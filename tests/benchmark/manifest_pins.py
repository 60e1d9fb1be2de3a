"""What ``BENCHMARK.json`` holds of the PRs that wrote it, as checks of a
manifest dict: the one place that pins the order of its entries.  Each
admits what a later PR appends (a cell, a configuration, a metric, a
cell's name at the end of a metric's ``workloads``) and fails where an
entry that was there is taken away, changed or moved: entries are added
at the end, and a test of this directory may not be edited by the PR
that adds them.  No test of this directory reads the committed lists
from their end (``test_bench_appends.py``)."""

from benchmark import manifest

CELL = "ragged_backlog"
#: PR 28's six, ``ragged_backlog``'s own
NEW = ["tx_per_block", "verify_lane_fill", "verify_roofline_sum",
       "caller_ms_per_ktx", "commit_ms_per_ktx", "apply_ms_per_ktx"]
SBE = "sbe_backlog"
#: ``sbe_backlog``'s own three
SBE_OWN = ["key_lanes_ms", "key_policy_entries_per_block", "apply_meta_rows"]
#: the WAL checkpoints (the txid index's, the state and history DBs'), a
#: mean a block in every backlog cell
CHECKPOINTS = ["commit_checkpoint_ms", "ledger_checkpoint_ms"]
#: ``per_layer`` begins with these, in this order
PER_LAYER = [
    "pipeline_overlap_coverage", "launch_self_ms", "state_fill_ms",
    "host_lane_rest_ms", "device_wait_ms", "h2d_bytes_per_block",
    "verify_kernel_ms", "stage2_kernel_ms", "verify_roofline",
    "ledger_commit_ms", "valid_share", "generator_lag_p95_ms",
    "paced.launch_self_ms", "paced.state_fill_ms", "paced.ledger_commit_ms",
    "apply_lag_ms", "dup_txid_ms", "state_gather_ms",
    "state_gather_under_apply", "commit_index_ms",
    "commit_enqueue_ms", "apply_write_ms", "apply_history_ms",
    "paced.dup_txid_ms", "paced.apply_write_ms", "paced.apply_history_ms",
    "paced.feed_wait_ms", "commit_index_growth"] + NEW + SBE_OWN + [
    CHECKPOINTS[0], CHECKPOINTS[1], "paced.ledger_checkpoint_ms"]
#: what ``insert_backlog`` reports and ``ragged_backlog`` does not: one
#: execution's time or one launch's frame, medians over the window,
#: which on launches of seven and twelve shapes are one shape's
ONE_SHAPE = {"h2d_bytes_per_block", "verify_kernel_ms", "stage2_kernel_ms",
             "verify_roofline"}
OLD_CELLS = ["rw_backlog", "insert_backlog", "zipf_backlog"]
ONLY_PACED = {"generator_lag_p95_ms", "apply_lag_ms"}
#: ``end_to_end`` begins with these: name, bound, the cells it lists.
#: The paced pair's bounds were set anew from two sets of six runs
#: (PERF.md, section 2)
END_TO_END = [
    ("commit_tx_per_s", 0.07, OLD_CELLS + [CELL]),
    ("tx_commit_p50_ms", 0.05, ["rw_paced"]),
    ("tx_commit_p95_ms", 0.08, ["rw_paced"]),
    ("setup_s", 0.25, None),
]
#: cell → (configuration, traffic file, chips), in the order listed
CELLS = {
    "rw_backlog": ("fabric-2of3-sqlite1m", "rw-backlog", 1),
    "insert_backlog": ("fabric-2of3-sqlite1m", "insert-backlog", 1),
    "rw_paced": ("fabric-2of3-sqlite1m", "rw-paced", 1),
    "zipf_backlog": ("fabric-zipf10k-sqlite", "zipf-backlog", 1),
    CELL: ("fabric-default-cutter", "insert-cutter-backlog", 1),
    SBE: ("fabric-sbe-assets", "sbe-backlog", 1),
}
#: configuration → what of it was cut
CONFIGS = {
    "fabric-2of3-sqlite1m": ["preload_keys", "channels",
                             "verify_orderer_block_signature"],
    "fabric-zipf10k-sqlite": ["channels", "verify_orderer_block_signature"],
    "fabric-default-cutter": ["preload_keys", "channels",
                              "verify_orderer_block_signature"],
    "fabric-sbe-assets": ["preload_keys", "channels",
                          "verify_orderer_block_signature"],
}


def _pinned_cells(name: str) -> list:
    """The cells a pinned per-layer metric listed when it was added."""
    if name in NEW:
        return [CELL]
    if name in SBE_OWN:
        return [SBE]
    if name in CHECKPOINTS:
        return OLD_CELLS + [CELL, SBE]
    if name.startswith("paced.") or name in ONLY_PACED:
        return ["rw_paced"]
    return OLD_CELLS + [CELL] * (name not in ONE_SHAPE)


def per_layer_begins_with_what_was_there(man: dict) -> None:
    names = [m["name"] for m in man["per_layer"]]
    assert names[:len(PER_LAYER)] == PER_LAYER


def each_metric_still_lists_its_cells_first(man: dict) -> None:
    by = {m["name"]: m for m in man["per_layer"]}
    for name in PER_LAYER:
        want = _pinned_cells(name)
        assert by[name]["workloads"][:len(want)] == want, name
        assert by[name]["moves"] == (
            "tx_commit_p50_ms" if want == ["rw_paced"]
            else "commit_tx_per_s"), name


def end_to_end_keeps_its_metrics_and_bounds(man: dict) -> None:
    got = man["end_to_end"][:len(END_TO_END)]
    assert [m["name"] for m in got] == [e[0] for e in END_TO_END]
    for m, (name, bound, cells) in zip(got, END_TO_END):
        assert m["bound"] == bound, name
        if cells is None:
            assert "workloads" not in m, name
        else:
            assert m["workloads"][:len(cells)] == cells, name


def cells_and_configurations_are_still_there(man: dict) -> None:
    cells = {w["name"]: w for w in man["workloads"]}
    assert [w["name"] for w in man["workloads"]][:len(CELLS)] == list(CELLS)
    for name, want in CELLS.items():
        w = cells[name]
        assert (w["config"], w["traffic"], w["chips"]) == want, name
    configs = {c["name"]: c for c in man["configs"]}
    assert [c["name"] for c in man["configs"]][:len(CONFIGS)] == list(CONFIGS)
    for name, reduced in CONFIGS.items():
        c = configs[name]
        assert c["file"] == f"benchmark/configs/{name}.json", name
        assert c["reduced"] == reduced, name


def ragged_backlog_reports_its_siblings_metrics_but_one_shapes(
        man: dict) -> None:
    """Of the pinned metrics the cell reports ``insert_backlog``'s less
    ``ONE_SHAPE``, and its own six; what a later PR adds is its own."""
    pinned = set(PER_LAYER)
    mine = {m["name"] for m in manifest.metrics_of(man, "per_layer", CELL)}
    theirs = {m["name"] for m in manifest.metrics_of(
        man, "per_layer", "insert_backlog")}
    assert mine & pinned == ((theirs & pinned) - ONE_SHAPE) | set(NEW)
    e2e = [m["name"] for m in manifest.metrics_of(man, "end_to_end", CELL)]
    assert [n for n in e2e if n in {e[0] for e in END_TO_END}] == [
        "commit_tx_per_s", "setup_s"]


PINS = (per_layer_begins_with_what_was_there,
        each_metric_still_lists_its_cells_first,
        end_to_end_keeps_its_metrics_and_bounds,
        cells_and_configurations_are_still_there,
        ragged_backlog_reports_its_siblings_metrics_but_one_shapes)
