"""The benchmark of the block-commit path: the yardstick, kept apart
from the program so that no later PR can move it.

``run.py`` runs one cell (``BENCHMARK.json`` → ``workloads``) once on
the chip.  Whatever belongs to one configuration, one traffic mix, one
generator or one metric sits in a file of its own that the harness finds
by the name the manifest gives: ``configs/``, ``traffic/``,
``generators/``, ``end_to_end/``, ``layer_metrics/``; a configuration
that starts from another ledger than keys at block 0, or is held to
other verdicts, names its own ``preloads/<name>.py`` and
``references/<name>.py``.  The plain reference (``reference.py``), the
default preload (``preload.py``), the window and percentile arithmetic
(``timeline.py``), the trace reduction (``trace_reduce.py``) and the
peaks and operation counts (``roofline.py``) are shared by all of them.
"""
