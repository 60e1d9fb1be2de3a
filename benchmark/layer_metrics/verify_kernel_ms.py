"""Device time of one execution of the verify XLA module
(``jit_verify_batch_packed``), median over the executions that began
inside the captured window."""

from benchmark import trace_reduce

LAYER, UNIT, SOURCE, MOVES = ("kernels", "ms", "device_trace",
                              "commit_tx_per_s")
MODULE = "jit_verify_batch_packed"


def read(obs):
    return trace_reduce.median_run_ms(obs, MODULE)
