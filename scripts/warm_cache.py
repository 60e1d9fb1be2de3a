"""Persistent-compile-cache warm-up driven by the launch ledger.

A restarted peer (or a fresh snapshot-join peer about to replay its
chain suffix) pays a cold XLA compile for every kernel shape its
traffic touches — the launch ledger (observe/ledger.py) records those
as ``cache: "miss"`` rows with multi-second ``compile_ms``.  This tool
closes the loop: feed it a ledger report (the ``/launches`` operations
endpoint, or a ``BENCH_*.json`` line's ``extras.device_ledger``), and
it re-dispatches every compile-missed verify/sign shape with dummy
lanes AFTER arming the repo's persistent compile cache
(utils/xla_env.enable_compile_cache: ``JAX_COMPILATION_CACHE_DIR``
where set, else ``<checkout>/.jax_cache``), so the next
process to hit those shapes loads the compiled program from disk
instead of tracing it on the serving path.

Only the standalone crypto kernels are reconstructable from a
(kernel, lanes) row alone:

* ``verify`` (ops/p256v3): one genuinely valid (e, r, s, qx, qy)
  tuple — produced by the host signer, no ``cryptography`` needed —
  replicated ``lanes`` times; the bucket/chunk padding reproduces the
  recorded structural shape.
* ``sign`` (ops/p256sign): the fixed-base comb ladder over ``lanes``
  dummy digests.

``stage2`` and ``resident_scatter`` rows are skipped with a note:
their shapes embed live validator state (read-set layout, resident
table geometry) that a report row does not carry — the first real
block recompiles those, and the verify/sign warms already cover the
dominant compile cost (see the ledger's per-kernel compile_ms).

The chunk / recode / mesh knobs are NOT in ledger rows either; pass
the serving configuration via flags (mirroring FABTPU_BENCH_RECODE /
FABTPU_BENCH_VERIFY_CHUNK) so the warmed structural keys match.

Usage:
    python scripts/warm_cache.py LAUNCHES.json [--chunk N] [--recode]
    python scripts/warm_cache.py BENCH_r06_block_commit.json
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: kernels whose structural shape a report row fully determines
RECONSTRUCTABLE = ("verify", "sign")


def load_report(path: str) -> dict:
    """Accept a raw ledger report, a ``/launches`` body, or a full
    bench JSON line (``extras.device_ledger``)."""
    with open(path) as f:
        doc = json.load(f)
    if "extras" in doc and isinstance(doc["extras"], dict):
        led = doc["extras"].get("device_ledger")
        if led is None:
            raise SystemExit(f"{path}: no extras.device_ledger section")
        return led
    return doc


def miss_shapes(report: dict) -> tuple[dict, list]:
    """(kernel → sorted lane counts that compile-missed, skipped
    kernel notes).  Reads the raw ``recent`` rows — the per-kernel
    stats aggregate away the lane counts the re-dispatch needs."""
    shapes: dict[str, set] = {}
    for row in report.get("recent", ()):
        if row.get("cache") != "miss":
            continue
        shapes.setdefault(row["kernel"], set()).add(int(row["lanes"]))
    skipped = [
        {"kernel": k, "lanes": sorted(v),
         "note": "shape depends on live validator state; first real "
                 "block recompiles it"}
        for k, v in shapes.items() if k not in RECONSTRUCTABLE
    ]
    # aggregated fallback: a kernel with recorded misses whose raw
    # rows already rotated out of the ring — report it rather than
    # silently claiming full coverage
    for k, st in report.get("kernels", {}).items():
        if st.get("cache_misses") and k not in shapes:
            skipped.append({"kernel": k, "lanes": [],
                            "note": "misses recorded but raw rows "
                                    "rotated out of the ring; rerun "
                                    "with a larger rows= report"})
    return {k: sorted(v) for k, v in shapes.items()
            if k in RECONSTRUCTABLE}, skipped


def warm_verify(lanes: int, chunk: int, recode: bool) -> None:
    from fabric_tpu.ops import p256sign, p256v3

    key = 0xC0FFEE + 1  # any scalar in [1, n-1]
    e = 0x5EED
    r, s = p256sign.sign_host([e], key)[0]
    qx, qy = p256sign._pub_of(key)
    items = [(e, r, s, qx, qy)] * lanes
    ok = p256v3.verify_launch(items, chunk=chunk or None,
                              recode_device=recode)()
    assert all(ok), "warm-up verify rejected a valid signature"


def warm_sign(lanes: int, chunk: int) -> None:
    from fabric_tpu.ops import p256sign

    sigs = p256sign.sign_launch([0x5EED] * lanes, 0xC0FFEE + 1,
                                chunk=chunk or None).fetch()
    assert len(sigs) == lanes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("report", help="/launches JSON, ledger report, or "
                                   "bench JSON with extras.device_ledger")
    ap.add_argument("--chunk", type=int, default=0,
                    help="verify microbatch size of the serving config "
                         "(FABTPU_BENCH_VERIFY_CHUNK); 0 = monolithic")
    ap.add_argument("--sign-chunk", type=int, default=0,
                    help="sign microbatch size; 0 = monolithic")
    ap.add_argument("--recode", action="store_true",
                    help="serving config ships limbs + recodes windows "
                         "on device (FABTPU_BENCH_RECODE=1)")
    args = ap.parse_args(argv)

    # arm the persistent cache BEFORE any kernel builds — this is the
    # entire point: the warm dispatches below populate it
    from fabric_tpu.utils.xla_env import enable_compile_cache

    cache_dir = enable_compile_cache()
    shapes, skipped = miss_shapes(load_report(args.report))

    warmed, failed = [], []
    for kernel, lane_counts in sorted(shapes.items()):
        for lanes in lane_counts:
            try:
                if kernel == "verify":
                    warm_verify(lanes, args.chunk, args.recode)
                else:
                    warm_sign(lanes, args.sign_chunk)
                warmed.append({"kernel": kernel, "lanes": lanes})
            except Exception as e:  # keep warming the rest
                failed.append({"kernel": kernel, "lanes": lanes,
                               "error": str(e)})
    print(json.dumps({
        "cache_dir": cache_dir,
        "warmed": warmed,
        "skipped": skipped,
        "failed": failed,
    }, indent=2))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
