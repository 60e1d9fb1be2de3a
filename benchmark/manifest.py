"""``BENCHMARK.json`` and the files it names, found by name.

Every lookup takes the checkout ``root``: the tests run the harness on
a temporary copy that holds files the repository does not.
"""

from __future__ import annotations

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: this directory's name under the checkout root
HERE = os.path.basename(os.path.dirname(os.path.abspath(__file__)))


class ManifestError(ValueError):
    """The manifest names something the benchmark's files do not hold."""


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(root: str = ROOT) -> dict:
    return _read_json(os.path.join(root, "BENCHMARK.json"))


def cell(manifest: dict, name: str, root: str = ROOT) -> tuple:
    """→ (workload entry, configuration dict, traffic dict)."""
    workloads = {w["name"]: w for w in manifest["workloads"]}
    if name not in workloads:
        raise ManifestError(
            f"no workload {name!r}; BENCHMARK.json has {sorted(workloads)}")
    w = workloads[name]
    files = {c["name"]: c["file"] for c in manifest["configs"]}
    if w["config"] not in files:
        raise ManifestError(
            f"workload {name!r}: no config {w['config']!r} in BENCHMARK.json")
    config = _read_json(os.path.join(root, files[w["config"]]))
    traffic = _read_json(
        os.path.join(root, HERE, "traffic", w["traffic"] + ".json"))
    return w, config, traffic


def metrics_of(manifest: dict, section: str, cell_name: str) -> list:
    """The entries of ``end_to_end`` / ``per_layer`` this cell reports:
    those without a ``workloads`` list, and those whose list names it."""
    return [m for m in manifest[section]
            if cell_name in m.get("workloads", [cell_name])]


def load_module(kind: str, name: str, root: str = ROOT):
    """The file ``<root>/benchmark/<kind>/<name>.py`` as a module.  By
    path, so that a name with a dot or a dash in it is a file like any
    other, and a file added to a copy of the tree is found there."""
    path = os.path.join(root, HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise ManifestError(f"{kind} {name!r}: no file {path}")
    ident = "".join(c if c.isalnum() else "_" for c in f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(f"_bench_{ident}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_of(config: dict, root: str = ROOT):
    """The plain reference a configuration is held to: the module
    ``benchmark/references/<name>.py`` where it gives ``"reference"``,
    else ``benchmark/reference.py``.  Same functions either way."""
    name = config.get("reference")
    if name:
        return load_module("references", name, root)
    from benchmark import reference

    return reference


def preload_of(config: dict, root: str = ROOT):
    """The module whose ``ensure(cache_dir, config, seed)`` builds what a
    run starts from: ``benchmark/preloads/<name>.py`` where the
    configuration gives ``"preload"``, else ``benchmark/preload.py``."""
    name = config.get("preload")
    if name:
        return load_module("preloads", name, root)
    from benchmark import preload

    return preload
