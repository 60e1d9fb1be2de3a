"""The validator's other host stages, summed, median per block:
``host_parse``, ``sig_prepare_launch``, ``device_pre``, ``hd_frame``
(prefetch thread) and ``stage2_dispatch``, ``postprocess`` (caller
thread)."""

from benchmark import spans

LAYER, UNIT, SOURCE, MOVES = ("validator.host_lane", "ms", "program_span",
                              "commit_tx_per_s")
STAGES = ("host_parse", "sig_prepare_launch", "device_pre", "hd_frame",
          "stage2_dispatch", "postprocess")


def read(obs):
    return spans.median_ms(spans.ms_per_block(obs.roots, STAGES))
