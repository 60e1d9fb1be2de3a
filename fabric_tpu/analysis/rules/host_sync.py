"""FT003 host-sync-in-hot-path: device syncs on the commit path.

The validator pipeline earns its throughput by keeping exactly ONE
host-device sync per block (the packed stage-2 readback).  Any stray
``.block_until_ready()`` / ``jax.device_get`` / ``.item()`` / direct
``np.asarray(<call>)`` readback inside the commit call graph
serializes the pipeline and shows up only as a bench regression.

The rule builds a project-wide call graph rooted at the functions of
``peer/validator.py`` and ``peer/coordinator.py`` and flags sync
constructs in every reachable function.  Resolution is IMPORT-AWARE:

* ``p256v3.verify_host()`` where ``p256v3`` was imported from an analyzed
  module links only to THAT module's ``verify_host`` def — not to
  every same-named def in the project;
* ``from mod import foo`` (incl. ``as`` renames and relative imports,
  collected from function bodies too) links a bare ``foo()`` call only
  to ``mod``'s def;
* calls through names imported from clearly-EXTERNAL modules (numpy,
  jax, stdlib — nothing analyzed shares their root package) produce no
  edges at all;
* anything unresolvable (``self.foo()``, locals, project-looking
  imports that did not resolve) falls back to bare-name linking —
  deliberately over-approximate, never under.

Intended sync points carry a ``# fabtpu: noqa(FT003)`` with a comment
saying why.
"""

from __future__ import annotations

import ast
from collections import deque

from fabric_tpu.analysis.core import (
    Finding,
    ModuleCtx,
    Rule,
    call_name,
    dotted_name,
    register,
    walk_functions,
)

_ROOT_MODULES = ("peer/validator.py", "peer/coordinator.py")

_SYNC_ATTRS = {"block_until_ready", "item"}
_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready"}
_READBACK_CONVERTERS = {"np.asarray", "np.array", "numpy.asarray",
                        "numpy.array"}
# builtins whose result is host memory by construction — converting
# them is a copy at worst, never a device sync
_HOST_PRODUCERS = {
    "sorted", "list", "tuple", "set", "dict", "range", "zip", "len",
    "enumerate", "min", "max", "sum", "reversed",
}


def _fn_key(mod: ModuleCtx, fn: ast.FunctionDef) -> tuple[str, str, int]:
    # lineno disambiguates same-named methods on different classes
    return (mod.relpath, fn.name, fn.lineno)


def _dotted_of(relpath: str) -> str:
    """Module relpath → dotted form ("fabric_tpu/ops/p256v3.py" →
    "fabric_tpu.ops.p256v3"; packages drop the __init__ leaf)."""
    p = relpath[:-3] if relpath.endswith(".py") else relpath
    if p.endswith("/__init__"):
        p = p[: -len("/__init__")]
    return p.replace("/", ".")


class _ModuleIndex:
    """Resolves import dotted paths to analyzed module relpaths.

    Matching is suffix-tolerant in both directions because the
    analysis root is not necessarily the import root: analyzing from
    the repo root gives dotted forms like ``fabric_tpu.ops.p256v3``
    while analyzing the package directory gives ``ops.p256v3`` — both
    must resolve ``from fabric_tpu.ops import p256v3``."""

    def __init__(self, modules: list[ModuleCtx]):
        self._dotted = [(_dotted_of(m.relpath), m.relpath)
                        for m in modules]
        # package segments of the analyzed set: imports sharing none
        # of these are clearly external.  The analysis ROOT's own
        # directory name rides along because absolute imports name the
        # super-package even when the root IS the package directory
        # (root=fabric_tpu/ gives dotted forms like "ops.p256v3", yet
        # code says "from fabric_tpu.ops import p256v3" — without this,
        # an unresolvable absolute project import would be classified
        # external and silently under-approximate the graph).
        self.roots = set()
        for d, _ in self._dotted:
            self.roots.update(d.split("."))
        import os

        for m in modules:
            if m.path != m.relpath and m.path.endswith(m.relpath):
                root_dir = m.path[: -len(m.relpath)].rstrip("/\\")
                base = os.path.basename(root_dir)
                if base:
                    self.roots.add(base)

    def resolve(self, dotted: str) -> list[str]:
        if not dotted:
            return []
        out = []
        for d, rel in self._dotted:
            if d == dotted or d.endswith("." + dotted) or \
                    dotted.endswith("." + d):
                out.append(rel)
        return out

    def maybe_project(self, dotted: str) -> bool:
        return bool(dotted) and dotted.split(".")[0] in self.roots


# alias-entry shapes:
#   ("mod", rel)           alias IS analyzed module rel (attr calls link there)
#   ("obj", rel, name)     alias is object `name` imported from module rel;
#                          degrades to bare-name when rel has no such def
#                          (package re-exports must not blind the graph)
#   ("objsoft", rel, name) same, but only a hedge beside a real submodule
#                          match — links iff the def exists, never degrades
#   ("prefix",)            plain `import a.b` — re-resolve from the call's
#                          full dotted path at edge time
#   ("any",)               project-looking but unresolved → bare fallback
# an alias mapping to [] is a KNOWN-external import → no edges at all


def _pkg_parts(relpath: str) -> list[str]:
    parts = relpath.split("/")[:-1]
    if relpath.endswith("/__init__.py"):
        parts = parts[:-1]
    return parts


def _import_aliases(mod: ModuleCtx, index: _ModuleIndex) -> dict:
    """name → alias entries, from every import statement in the module
    (function-local imports included — this codebase imports lazily on
    hot paths)."""
    aliases: dict[str, list] = {}

    def add(name: str, entries: list) -> None:
        aliases.setdefault(name, []).extend(entries)

    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    rels = index.resolve(a.name)
                    if rels:
                        add(a.asname, [("mod", r) for r in rels])
                    elif index.maybe_project(a.name):
                        add(a.asname, [("any",)])
                    else:
                        aliases.setdefault(a.asname, [])
                else:
                    head = a.name.split(".")[0]
                    if index.maybe_project(a.name):
                        add(head, [("prefix",)])
                    else:
                        aliases.setdefault(head, [])
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            relative = node.level > 0
            if relative:
                parts = _pkg_parts(mod.relpath)
                if node.level > 1:
                    parts = parts[: -(node.level - 1)] or parts[:1]
                base = ".".join(parts + ([node.module] if node.module
                                         else []))
            mod_rels = index.resolve(base)
            projecty = relative or index.maybe_project(base)
            for a in node.names:
                if a.name == "*":
                    continue
                local = a.asname or a.name
                sub_rels = index.resolve(f"{base}.{a.name}" if base
                                         else a.name)
                entries = [("mod", r) for r in sub_rels]
                # the imported name may be an object in the package
                # module instead of (or shadowing) a submodule; when a
                # submodule DID match, the object entry is only a soft
                # hedge — it must not degrade resolution if the
                # package has no such def
                kind = "objsoft" if sub_rels else "obj"
                entries += [(kind, r, a.name) for r in mod_rels]
                if entries:
                    add(local, entries)
                elif projecty:
                    add(local, [("any",)])
                else:
                    aliases.setdefault(local, [])
    return aliases


@register
class HostSyncRule(Rule):
    id = "FT003"
    name = "host-sync-in-hot-path"
    severity = "error"
    description = (
        "flags device syncs (block_until_ready/device_get/.item()/"
        "np.asarray(<call>)) reachable from the validator/commit graph"
    )
    # overridable in tests
    root_modules: tuple[str, ...] = _ROOT_MODULES
    # how many root functions the last check_project seeded the BFS
    # with — tests pin this > 0 over fabric_tpu/ so a rename of the
    # root modules cannot silently turn the rule into a no-op
    last_root_count: int = 0

    def check_project(self, modules: list[ModuleCtx]) -> list[Finding]:
        index = _ModuleIndex(modules)

        # 1. collect every function def, keyed by bare name and by
        #    (module, name) for import-resolved edges
        defs: dict[tuple, ast.FunctionDef] = {}
        by_name: dict[str, list[tuple]] = {}
        by_mod_name: dict[tuple[str, str], list[tuple]] = {}
        mod_of: dict[tuple, ModuleCtx] = {}
        for mod in modules:
            for fn in walk_functions(mod.tree):
                key = _fn_key(mod, fn)
                defs[key] = fn
                mod_of[key] = mod
                by_name.setdefault(fn.name, []).append(key)
                by_mod_name.setdefault((mod.relpath, fn.name), []).append(key)

        # 2. edges: function → resolution targets
        #    ("name", bare) links every same-named def;
        #    ("mod", rel, bare) links only rel's defs
        alias_cache: dict[str, dict] = {}

        def targets_of(mod: ModuleCtx, name: str) -> list[tuple]:
            aliases = alias_cache.get(mod.relpath)
            if aliases is None:
                aliases = alias_cache[mod.relpath] = _import_aliases(
                    mod, index
                )
            bare = name.split(".")[-1]
            head = name.split(".")[0]
            is_attr = "." in name
            if head not in aliases:
                return [("name", bare)]

            def resolved(rel: str, nm: str) -> tuple:
                # a resolved module WITHOUT a def of that name means
                # the name is re-exported (`__init__` facades) or
                # synthesized — degrade to bare-name rather than drop
                # the edge: over-approximate, never under
                if (rel, nm) in by_mod_name:
                    return ("mod", rel, nm)
                return ("name", nm)

            out: list[tuple] = []
            for entry in aliases[head]:
                kind = entry[0]
                if kind == "mod":
                    # bare call of a module name is not a function
                    # call; the companion ("obj") entry covers the
                    # imported-class case
                    if is_attr:
                        out.append(resolved(entry[1], bare))
                elif kind == "obj":
                    # attr call through an imported class/object: its
                    # methods live where the object is defined
                    out.append(
                        resolved(entry[1], bare if is_attr else entry[2])
                    )
                elif kind == "objsoft":
                    # hedge beside a real submodule match: link only
                    # when the package module actually defines the
                    # name, never degrade through it
                    nm = bare if is_attr else entry[2]
                    if (entry[1], nm) in by_mod_name:
                        out.append(("mod", entry[1], nm))
                elif kind == "prefix" and is_attr:
                    dotted = name.rsplit(".", 1)[0]
                    rels = index.resolve(dotted)
                    if rels:
                        out.extend(resolved(r, bare) for r in rels)
                    elif index.maybe_project(dotted):
                        return [("name", bare)]
                elif kind == "any":
                    return [("name", bare)]
            # a local def can shadow an import — keep the same-module
            # edge so added precision can never drop a real callee
            out.append(("mod", mod.relpath, bare))
            return out

        calls_of: dict[tuple, list[tuple]] = {}
        for key, fn in defs.items():
            mod = mod_of[key]
            seen: set[tuple] = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Call):
                    name = call_name(node)
                    if name:
                        seen.update(targets_of(mod, name))
            calls_of[key] = list(seen)

        # 3. BFS from the root modules' functions
        roots = [
            key for key, mod in mod_of.items()
            if any(mod.relpath.endswith(r) for r in self.root_modules)
        ]
        self.last_root_count = len(roots)
        hot: set[tuple] = set(roots)
        queue = deque(roots)
        while queue:
            key = queue.popleft()
            for target in calls_of.get(key, ()):
                if target[0] == "name":
                    callees = by_name.get(target[1], ())
                else:
                    callees = by_mod_name.get((target[1], target[2]), ())
                for callee in callees:
                    if callee not in hot:
                        hot.add(callee)
                        queue.append(callee)

        # 4. flag sync constructs inside hot functions
        out: list[Finding] = []
        seen_f: set[tuple] = set()
        for key in hot:
            fn, mod = defs[key], mod_of[key]
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                msg = self._sync_message(node, fn.name)
                if msg is None:
                    continue
                fkey = (mod.relpath, node.lineno, node.col_offset)
                if fkey in seen_f:
                    continue
                seen_f.add(fkey)
                out.append(self.finding(
                    mod, node.lineno, node.col_offset, msg,
                ))
        return out

    @staticmethod
    def _sync_message(node: ast.Call, fname: str) -> str | None:
        name = call_name(node)
        if name in _SYNC_CALLS:
            return (
                f"'{name}' in '{fname}' is reachable from the "
                f"validator/commit graph — a host-device sync on the "
                f"hot path"
            )
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _SYNC_ATTRS
            and not node.args and not node.keywords
        ):
            base = dotted_name(node.func.value) or "<expr>"
            return (
                f"'.{node.func.attr}()' on '{base}' in '{fname}' is "
                f"reachable from the validator/commit graph — a "
                f"host-device sync on the hot path"
            )
        if (
            name in _READBACK_CONVERTERS
            and node.args and isinstance(node.args[0], ast.Call)
            and call_name(node.args[0]) not in _HOST_PRODUCERS
        ):
            inner = call_name(node.args[0]) or "<call>"
            return (
                f"'{name}({inner}(...))' in '{fname}' converts a fresh "
                f"call result to host memory on the validator/commit "
                f"graph — a device readback unless proven host-only"
            )
        return None
