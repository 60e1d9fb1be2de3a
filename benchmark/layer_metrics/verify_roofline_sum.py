"""The share of the chip's peak the verify ladder reaches on ragged
launches: the least time the chip could take for the real signatures of
the verify launches made inside the capture (``benchmark/roofline.py``),
summed, over the summed device time of the verify executions that began
inside it.  Padding counts as waste.  (``verify_roofline`` pairs a
median lane count with a median time, which on launches of different
sizes are different launches.)

No execution is paired with its launch: a capture names a program by
its fingerprint and nothing says which launch an execution was, so both
sums are taken over the capture's window, the launches' from the
``lanes`` of the blocks' ``sig_prepare_launch`` spans (PR 28), which end
at the dispatch, placed on the profiler's clock as the window filter of
every span metric places them.  An execution begins within milliseconds
of its dispatch (and the device's events can sit a few milliseconds off
the host's clock) where launches are 40 ms apart, so the two sets
differ by a launch at an edge in one capture of a few: one launch of
the hundred a capture holds.  None without a capture or where the spans
carry no ``lanes``."""

from benchmark import roofline, spans, trace_reduce

LAYER, UNIT, SOURCE, MOVES = ("kernels", "%", "device_trace",
                              "commit_tx_per_s")
MODULE = "jit_verify_batch_packed"


def read(obs):
    if obs.device_trace is None:
        return None
    t0, t1, offset = obs.capture_window
    seconds = sum(d for _dev, n, s, d in obs.device_trace.modules
                  if trace_reduce.module_name(n) == MODULE and t0 <= s <= t1)
    lanes = [int(sp.attrs["lanes"]) for r in obs.roots for sp in spans.walk(r)
             if sp.name == "sig_prepare_launch" and "lanes" in sp.attrs
             and t0 <= sp.t1 + offset <= t1]
    if not seconds or not lanes:
        return None
    least = sum(roofline.least_seconds(roofline.verify_work(n),
                                       obs.device_kind)[0] for n in lanes)
    return least / seconds * 100.0
