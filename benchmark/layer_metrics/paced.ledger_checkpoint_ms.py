"""``ledger_checkpoint_ms`` in the paced cell, where it should move the tx latency."""

from benchmark import manifest

_base = manifest.load_module("layer_metrics", "ledger_checkpoint_ms")
LAYER, UNIT, SOURCE = _base.LAYER, _base.UNIT, _base.SOURCE
MOVES = "tx_commit_p50_ms"
read = _base.read
