"""A later PR adds a metric as a reader file and an entry at the end of
``per_layer``, and edits no file that is there.  Two guards keep that
open: no test of this directory reads the committed manifest's
``per_layer``, ``workloads`` or ``configs`` from their end (the order is
pinned in one place, ``manifest_pins.py``, and from the start), and
every test of this directory that reads the manifest passes on a copy of
the tree to which a made-up metric was appended so."""

import ast
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

import fabric_tpu
from benchmark import manifest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = manifest.ROOT
LISTS = {"per_layer", "workloads", "configs"}
#: what a test that drives the harness calls: such a test reads the
#: manifest through a run, not through its lists
RUNS = ("make_root(", "run_cell(", "Rig(")


def _from_end(index) -> bool:
    def negative(node):
        return (isinstance(node, ast.UnaryOp)
                and isinstance(node.op, ast.USub)) or (
            isinstance(node, ast.Constant) and isinstance(node.value, int)
            and node.value < 0)

    if isinstance(index, ast.Slice):
        return any(b is not None and negative(b)
                   for b in (index.lower, index.upper))
    return negative(index)


def _bound_to_listed(func) -> set:
    """Names a function binds to ``listed()``: the manifest with the
    cells under ``benchmark/cells/`` appended on purpose, whose end is
    theirs."""
    out = set()
    for node in ast.walk(func):
        if not isinstance(node, ast.Assign):
            continue
        for target in node.targets:
            pairs = ([(target, node.value)] if isinstance(target, ast.Name)
                     else list(zip(target.elts, node.value.elts))
                     if isinstance(target, ast.Tuple)
                     and isinstance(node.value, ast.Tuple) else [])
            for name, value in pairs:
                if (isinstance(name, ast.Name) and isinstance(value, ast.Call)
                        and getattr(value.func, "id", None) == "listed"):
                    out.add(name.id)
    return out


def end_reads(source: str) -> list:
    """``(line, text)`` of each subscript from the end of a manifest's
    ``per_layer``, ``workloads`` or ``configs``, but those of a name
    bound to ``listed()`` in the same function."""
    tree = ast.parse(source)
    exempt = {}
    for func in ast.walk(tree):
        if isinstance(func, (ast.FunctionDef, ast.Lambda)):
            names = _bound_to_listed(func)
            for node in ast.walk(func):
                exempt.setdefault(id(node), set()).update(names)
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Subscript) and _from_end(node.slice)):
            continue
        inner = node.value
        if not (isinstance(inner, ast.Subscript)
                and isinstance(inner.slice, ast.Constant)
                and inner.slice.value in LISTS):
            continue
        if (isinstance(inner.value, ast.Name)
                and inner.value.id in exempt.get(id(node), ())):
            continue
        out.append((node.lineno, ast.get_source_segment(source, node)))
    return out


def test_the_rule_finds_reads_from_the_end_and_spares_listed():
    made_up = textwrap.dedent('''
        def f():
            man = manifest.load()
            a = man["per_layer"][-1]
            b = manifest.load()["workloads"][:-2]
            c = man["configs"][-3:]
            d = man["per_layer"][3]

        def g():
            was, man = manifest.load(), listed()
            return man["per_layer"][-1], was["per_layer"][-1]
        ''')
    got = [text for _line, text in end_reads(made_up)]
    assert got == ['man["per_layer"][-1]',
                   'manifest.load()["workloads"][:-2]',
                   'man["configs"][-3:]', 'was["per_layer"][-1]']


def test_no_test_reads_the_committed_lists_from_their_end():
    found = {}
    for path in sorted(glob.glob(os.path.join(HERE, "*.py"))):
        with open(path) as f:
            got = end_reads(f.read())
        if got:
            found[os.path.basename(path)] = got
    assert not found


# -- a made-up metric appended to a copy of the tree ----------------------------

READER = '''
"""A made-up metric appended as a later PR appends one: the state and
history DBs' WAL checkpoints in the window, from the program's counter."""
from benchmark import counters

LAYER, UNIT, SOURCE, MOVES = ("ledger", "1", "program_counter",
                              "commit_tx_per_s")


def read(obs):
    return counters.change(obs, "ledger_wal_checkpoints_total")
'''
ENTRY = {"name": "made_up_appended", "unit": "1", "better": "lower",
         "source": "program_counter", "layer": "ledger",
         "moves": "commit_tx_per_s",
         "workloads": ["rw_backlog", "insert_backlog", "zipf_backlog",
                       "ragged_backlog", "sbe_backlog"]}


def manifest_tests(root: str) -> list:
    """The tests of ``<root>/tests/benchmark`` that read the manifest
    itself (its lists, ``listed()`` or the pins) without driving the
    harness: pytest node ids from ``root``, this file's left out."""
    out = []
    tests_dir = os.path.join(root, "tests", "benchmark")
    for path in sorted(glob.glob(os.path.join(tests_dir, "test_*.py"))):
        if os.path.basename(path) == os.path.basename(__file__):
            continue
        with open(path) as f:
            source = f.read()
        for node in ast.parse(source).body:
            if not (isinstance(node, ast.FunctionDef)
                    and node.name.startswith("test_")):
                continue
            text = ast.get_source_segment(source, node)
            reads = ("manifest.load(" in text or "listed(" in text
                     or "manifest_pins" in text
                     or "man" in [a.arg for a in node.args.args])
            if reads and not any(r in text for r in RUNS):
                out.append(f"{os.path.relpath(path, root)}::{node.name}")
    return out


def test_a_metric_appended_as_a_file_and_an_entry_breaks_no_test(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copy(os.path.join(REPO, "pytest.ini"), root)
    skip = shutil.ignore_patterns(".cache", ".work", "__pycache__")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"), ignore=skip)
    shutil.copytree(HERE, os.path.join(root, "tests", "benchmark"),
                    ignore=skip)
    shutil.copy(os.path.join(REPO, "tests", "conftest.py"),
                os.path.join(root, "tests"))
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           ENTRY["name"] + ".py"), "w") as f:
        f.write(READER)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        man = json.load(f)
    man["per_layer"].append(ENTRY)
    with open(path, "w") as f:
        json.dump(man, f, indent=1)

    selected = manifest_tests(root)
    # the manifest's own tests, the pins and the checkpoints' entries
    # among them
    files = {s.split("::")[0] for s in selected}
    assert {"tests/benchmark/test_bench_manifest.py",
            "tests/benchmark/test_bench_checkpoint.py",
            "tests/benchmark/test_bench_sbe.py"} <= files
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PYTEST_", "PYTHONPATH"))}
    # the copy's ``benchmark`` first (the working directory), then the
    # program where this process found it
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(fabric_tpu.__file__)))
    done = subprocess.run(
        [sys.executable, "-m", "pytest", "-p", "no:cacheprovider",
         *selected],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    tail = done.stdout[-4000:] + done.stderr[-2000:]
    assert done.returncode == 0, tail
    passed = re.search(r"(\d+) passed", done.stdout)
    assert passed and int(passed.group(1)) >= len(selected), tail
    assert not re.search(r"\d+ (failed|error|skipped)", done.stdout), tail
    # the copy's tests read the copy's manifest: the made-up entry is last
    probe = subprocess.run(
        [sys.executable, "-c", "from benchmark import manifest; "
         "print(manifest.ROOT, manifest.load()['per_layer'][-1]['name'])"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert probe.stdout.split() == [root, ENTRY["name"]], probe.stderr
