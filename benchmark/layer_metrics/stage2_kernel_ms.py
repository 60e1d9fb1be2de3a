"""Device time of one execution of the fused stage-2 XLA module
(``jit_stage2``: policy reduction + MVCC fixpoint), median over the
executions that began inside the captured window."""

from benchmark import trace_reduce

LAYER, UNIT, SOURCE, MOVES = ("kernels", "ms", "device_trace",
                              "commit_tx_per_s")
MODULE = "jit_stage2"


def read(obs):
    return trace_reduce.median_run_ms(obs, MODULE)
