"""``BENCHMARK.json`` against the contract it is written to, and against
the files it names: what the driver would refuse before any run."""

import copy
import json
import os
import re

import pytest

import manifest_pins
from bench_tiny import listed
from benchmark import manifest, roofline

REPO = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module", params=["as_committed", "with_the_cells_"
                                        "measured_and_not_listed"])
def man(request):
    """The manifest, and what it becomes when the cells under
    ``benchmark/cells/*.json`` are listed: both must pass."""
    return manifest.load() if request.param == "as_committed" else listed()


def test_top_level_keys_and_limits(man):
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(man["run_seconds"], int) and 1 <= man["run_seconds"] <= 51
    assert 1 <= len(man["paths"]) <= 16 and len(man["command"]) <= 32
    assert 2 <= len(man["workloads"]) <= 24 and 1 <= len(man["configs"]) <= 24
    assert 1 <= len(man["end_to_end"]) <= 16 and 1 <= len(man["per_layer"]) <= 128
    for arg in man["command"]:
        assert not arg.startswith("/") and ".." not in arg
        if "/" in arg:
            assert any(arg.startswith(p + "/") for p in man["paths"])
    names = ([c["name"] for c in man["configs"]]
             + [w["name"] for w in man["workloads"]]
             + [m["name"] for m in man["end_to_end"] + man["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for entry in man["configs"] + man["workloads"]:
        assert len(entry["why"]) <= 200, entry["name"]


def test_files_under_paths_have_plain_names(man):
    plain = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in man["paths"]:
        for dirpath, dirnames, files in os.walk(os.path.join(REPO, p)):
            dirnames[:] = [d for d in dirnames
                           if d not in {"__pycache__", ".cache", ".work"}]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), REPO)
                assert plain.match(rel) and len(rel) <= 200, rel


def test_cells_name_files_that_exist(man):
    used, pairs = set(), set()
    files = [c["file"] for c in man["configs"]]
    assert len(files) == len(set(files))
    for w in man["workloads"]:
        _w, config, traffic = manifest.cell(man, w["name"])
        used.add(w["config"])
        pairs.add((w["config"], w["traffic"]))
        assert w["chips"] in (1, 4)
        assert config["name"] == w["config"] and traffic["name"] == w["traffic"]
        assert traffic["loop"] in ("backlog", "paced")
        assert hasattr(manifest.load_module("generators", traffic["generator"]),
                       "planner")
    assert used == {c["name"] for c in man["configs"]}
    assert len(pairs) == len(man["workloads"])
    assert sum(w["chips"] == 4 for w in man["workloads"]) <= max(
        1, len(man["workloads"]) // 2)
    for c in man["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in man["paths"]))
        with open(os.path.join(REPO, c["file"])) as f:
            doc = json.load(f)
        assert doc["source"] == c["source"].split()[0]
        # every cut of scale the manifest lists is explained in the file
        assert set(c["reduced"]) == set(doc["reduced"])
        assert all(k in doc for k in c["reduced"])


def test_every_cell_reports_what_the_contract_asks(man):
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert e2e["setup_s"]["bound"] == 0.25 and "workloads" not in e2e["setup_s"]
    for m in man["end_to_end"]:
        assert m["source"] in {"host_clock", "device_trace"}
        assert m["better"] in {"higher", "lower"} and 0.01 <= m["bound"] <= 0.25
    for w in man["workloads"]:
        mine = {m["name"] for m in manifest.metrics_of(man, "end_to_end",
                                                       w["name"])}
        assert "setup_s" in mine and len(mine) >= 2
        layers = manifest.metrics_of(man, "per_layer", w["name"])
        assert layers and all(m["moves"] in mine for m in layers)


def test_each_metric_has_a_reader_that_declares_what_the_manifest_says(man):
    for m in man["end_to_end"]:
        mod = manifest.load_module("end_to_end", m["name"])
        assert (mod.UNIT, mod.SOURCE) == (m["unit"], m["source"]), m["name"]
    for m in man["per_layer"]:
        assert m["source"] in SOURCES and m["moves"] in {
            e["name"] for e in man["end_to_end"]}
        assert LAYER.match(m["layer"]), m["name"]
        mod = manifest.load_module("layer_metrics", m["name"])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"]), m["name"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_a_name_the_manifest_does_not_hold_is_an_error(man):
    with pytest.raises(manifest.ManifestError):
        manifest.cell(man, "no_such_cell")
    with pytest.raises(manifest.ManifestError):
        manifest.load_module("layer_metrics", "no_such_metric")


# -- what earlier PRs brought stays where it was; what comes after is added --


@pytest.mark.parametrize("pin", manifest_pins.PINS,
                         ids=[p.__name__ for p in manifest_pins.PINS])
def test_the_manifest_holds_what_earlier_prs_brought(pin, man):
    pin(man)


def test_listing_long_chain_backlog_appends_and_moves_nothing():
    """``benchmark/cells/long_chain_backlog.json`` (measured, not listed:
    PERF.md section 7) adds a configuration, a cell and ``dup_txid_hits``
    at the end, and the cell's name at the end of the lists it joins."""
    was, man = manifest.load(), listed()
    assert man["configs"][:-1] == was["configs"]
    assert man["workloads"][:-1] == was["workloads"]
    assert man["workloads"][-1]["name"] == "long_chain_backlog"
    assert man["per_layer"][-1]["name"] == "dup_txid_hits"
    assert man["per_layer"][-1]["workloads"] == ["long_chain_backlog"]
    assert "dup_txid_hits" not in {m["name"] for m in was["per_layer"]}
    joined = 0
    for old, new in zip(was["end_to_end"] + was["per_layer"],
                        man["end_to_end"] + man["per_layer"]):
        if new != old:
            joined += 1
            assert new == dict(old, workloads=old["workloads"]
                               + ["long_chain_backlog"]), old["name"]
    assert joined == 22
    _w, config, traffic = manifest.cell(man, "long_chain_backlog")
    assert config["first_block"] == 10_000
    assert "duplicate_txid" in traffic["invalid_kinds"]


def _appended(man):
    """A made-up configuration, cell and per-layer metric at the end, and
    the cell's name at the end of the lists it joins: what the next PR of
    any kind does to ``BENCHMARK.json``."""
    man = copy.deepcopy(man)
    man["configs"].append({
        "name": "made-up-5org", "source": "https://example.org/spec",
        "file": "benchmark/configs/made-up-5org.json",
        "reduced": ["preload_keys"], "why": "a test's"})
    man["workloads"].append({
        "name": "made_up_backlog", "config": "made-up-5org",
        "traffic": "made-up-backlog", "chips": 1, "why": "a test's"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "insert_backlog" in m.get("workloads", ()):
            m["workloads"].append("made_up_backlog")
    man["per_layer"].append({
        "name": "made_up_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "validator.host_lane",
        "moves": "commit_tx_per_s",
        "workloads": ["made_up_backlog", "ragged_backlog",
                      "insert_backlog"]})
    return man


def test_an_appended_cell_configuration_and_metric_break_no_pin(man):
    for pin in manifest_pins.PINS:
        pin(_appended(man))


def _without(section, name):
    def change(man):
        man[section] = [e for e in man[section] if e["name"] != name]
    return change


def _swapped(section, i, j):
    def change(man):
        got = man[section]
        got[i], got[j] = got[j], got[i]
    return change


def _metric(section, name, **new):
    def change(man):
        for m in man[section]:
            if m["name"] == name:
                m.update({k: v(m[k]) if callable(v) else v
                          for k, v in new.items()})
    return change


BROKEN = {
    "a_per_layer_metric_removed": _without("per_layer", "state_gather_ms"),
    "two_per_layer_metrics_reordered": _swapped("per_layer", 3, 4),
    "a_metric_put_first": lambda man: man["per_layer"].insert(
        0, man["per_layer"].pop()),
    "a_cell_removed": _without("workloads", "ragged_backlog"),
    "two_cells_reordered": _swapped("workloads", 0, 1),
    "a_configuration_removed": _without("configs", "fabric-default-cutter"),
    "a_cell_taken_off_a_metrics_list": _metric(
        "per_layer", "apply_write_ms", workloads=lambda w: w[1:]),
    "a_cell_put_first_on_a_metrics_list": _metric(
        "per_layer", "tx_per_block",
        workloads=lambda w: ["made_up_backlog"] + w),
    "a_bound_loosened": _metric("end_to_end", "commit_tx_per_s", bound=0.1),
    "a_cells_traffic_changed": _metric("workloads", "rw_paced",
                                       traffic="rw-backlog"),
    "a_configurations_cuts_changed": _metric(
        "configs", "fabric-zipf10k-sqlite", reduced=["channels"]),
}


@pytest.mark.parametrize("how", sorted(BROKEN))
def test_a_pinned_entry_removed_changed_or_moved_fails_a_pin(how):
    man = _appended(manifest.load())
    BROKEN[how](man)
    failed = []
    for pin in manifest_pins.PINS:
        try:
            pin(man)
        except (AssertionError, KeyError):
            failed.append(pin.__name__)
    assert failed, how


def test_ladder_work_and_peaks():
    # by hand from the point formulas: 4 + 4 + 14*14 + 64*(4*13+14+13) + 5
    assert roofline.verify_muls_per_lane() == 5265
    assert roofline.MUL_FLOP == 12696
    work = roofline.verify_work(3000)
    assert work == {"flop": 3000 * 5265 * 12696, "bytes": 3000 * (314 * 2 + 1)}
    least, bound = roofline.least_seconds(work, "TPU v5 lite")
    assert bound == "compute" and least == pytest.approx(1.0179e-3, rel=1e-3)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
