"""The least time the chip could take to verify one block's signatures
(``benchmark/roofline.py``: matmul FLOP and bytes from the ladder's
shapes over the chip's peaks; the compute bound applies, 440 times over
the memory bound) over the verify module's device time.  Counted on the
block's real signatures (the ``lanes`` of its verify row in the launch
ledger; the median over the window's blocks, as the time is), so
padding to the bucket is waste."""

from benchmark import manifest, roofline, timeline

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "commit_tx_per_s")


def read(obs):
    kernel_ms = manifest.load_module(
        "layer_metrics", "verify_kernel_ms").read(obs)
    lanes = [r["lanes"] for r in obs.launch_rows if r["kernel"] == "verify"]
    if kernel_ms is None or not lanes:
        return None
    least, _bound = roofline.least_seconds(
        roofline.verify_work(timeline.median(lanes)), obs.device_kind)
    return least / (kernel_ms / 1000.0) * 100.0
