#!/usr/bin/env python
"""8-device virtual-mesh dryrun through the DECLARATIVE partition
rules (fabric_tpu/parallel/mesh.py).

What one run proves, in order:

1. the partition-rule table resolves and prints — every stage-2
   operand family has a declared PartitionSpec;
2. the ``MeshTopology(shape="8")`` path builds the 8-wide data mesh
   (the same resolution a pod-scale ``mesh_shape`` nodeconfig knob
   takes, minus ``jax.distributed``);
3. every data-sharded family actually places axis 0 across all 8
   devices — and the replicated family does not;
4. the key-range residency layout balances: ~512 keys over a
   1024-slot 8-shard table occupy EVERY shard with max/mean
   occupancy skew ≤ 2.0;
5. a mesh resize (8 → 4) reshards to a state identical to a manager
   born at 4 shards;
6. the full sharded ≡ unsharded kernel differential
   (``__graft_entry__.dryrun_multichip``): sha256, MVCC fixpoint,
   ECDSA verify, and the fused stage-2 program, bit-equal per lane.

Exit 0 = all green.  ``--out MULTICHIP_rNN.json`` records the run
(the repo keeps ``MULTICHIP_r06.json``) with ``extras.shard_balance``.
"""

import json
import os
import sys

N_DEVICES = int(os.environ.get("FABTPU_DRYRUN_DEVICES", "8"))

# the virtual-device pins must land before ANY jax import
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=%d" % N_DEVICES
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


def run() -> dict:
    import jax.numpy as jnp
    import numpy as np

    import __graft_entry__ as graft

    from fabric_tpu.ledger.statedb import MemVersionedDB, UpdateBatch
    from fabric_tpu.parallel import mesh as pmesh
    from fabric_tpu.parallel.topology import MeshTopology
    from fabric_tpu.state import ResidencyManager, build_launch_pack

    # 1. the rule table
    table = pmesh.rules_table()
    print("partition rules (%d families):" % len(table))
    for row in table:
        print("  %-17s %-14s %s"
              % (row["family"], row["spec"], row["description"][:48]))

    # 2. declarative topology → the 8-wide data mesh
    mesh = pmesh.resolve_fabric(MeshTopology(shape=str(N_DEVICES)))
    assert mesh is not None, "mesh_shape resolution returned no mesh"
    assert pmesh.data_axis_size(mesh) == N_DEVICES, dict(mesh.shape)
    print("mesh: %s (data axis = %d)"
          % (dict(mesh.shape), pmesh.data_axis_size(mesh)))

    # 3. per-family placement
    for row in table:
        fam = row["family"]
        arr = pmesh.shard(
            mesh, fam, jnp.zeros((N_DEVICES * 4, 3), jnp.int32)
        )
        if pmesh.rule_for(fam).replicated:
            assert arr.sharding.is_fully_replicated, fam
        else:
            assert len(arr.sharding.device_set) == N_DEVICES, (
                fam, arr.sharding
            )
    assert not pmesh.fallback_stats().get("ragged_axis0", 0), (
        "the bucketed dryrun shapes must never hit the ragged fallback"
    )
    print("placement: all %d families correct" % len(table))

    # 4. key-range balance on the sharded resident table
    n_keys = 512
    state = MemVersionedDB()
    b = UpdateBatch()
    for u in range(n_keys):
        b.put("ns", "key%04d" % u, b"v", (1, u))
    state.apply_updates(b, (1, 0))
    res = ResidencyManager(slots=1024, range_bits=10, mesh=mesh)
    assert res.stats()["shards"] == N_DEVICES
    pairs = [("ns", "key%04d" % u) for u in range(n_keys)]
    out = build_launch_pack(res, pairs, state)
    assert out is not None
    balance = res.shard_balance()
    assert sum(balance["per_shard_keys"]) == n_keys
    assert all(k > 0 for k in balance["per_shard_keys"]), (
        "an empty shard at 512 keys over 8 ranges-of-ranges means the "
        "blake2b range→shard map broke", balance
    )
    skew = balance["imbalance_max_over_mean"]
    assert skew <= 2.0, ("key-range occupancy skew too high", balance)
    # ownership law: every slot sits in its range's shard block
    slots, _t = res.lookup(pairs)
    sps = balance["slots_per_shard"]
    for pr, slot in zip(pairs, slots):
        rid = res.range_of(*pr)
        own = (rid * N_DEVICES) >> res.range_bits
        assert slot // sps == own, (pr, int(slot), own)
    print("shard balance: keys/shard=%s skew=%.3f"
          % (balance["per_shard_keys"], skew))

    # 5. mesh-resize reshard ≡ fresh manager at the new size
    half = pmesh.resolve_mesh(N_DEVICES // 2)
    st = res.reshard(half)
    assert st["enabled"] and st["resident_keys"] == 0
    fresh = ResidencyManager(slots=1024, range_bits=10, mesh=half)
    for r in (res, fresh):
        build_launch_pack(r, pairs, state)
    s1, t1 = res.lookup(pairs)
    s2, t2 = fresh.lookup(pairs)
    assert np.array_equal(s1, s2)
    assert np.array_equal(np.asarray(t1)[s1], np.asarray(t2)[s2])
    print("reshard %d -> %d: identical post-rebuild state"
          % (N_DEVICES, N_DEVICES // 2))

    # 6. the sharded ≡ unsharded kernel differential
    graft.dryrun_multichip(N_DEVICES)
    print("dryrun_multichip(%d): sharded == unsharded on every lane"
          % N_DEVICES)

    return {
        "rules": len(table),
        "shard_balance": {
            "data_axis": N_DEVICES,
            "per_shard_keys": balance["per_shard_keys"],
            "slots_per_shard": balance["slots_per_shard"],
            "occupancy_max": balance["occupancy_max"],
            "occupancy_mean": balance["occupancy_mean"],
            "imbalance_max_over_mean": skew,
        },
    }


def main(argv) -> int:
    out_path = None
    if "--out" in argv:
        out_path = argv[argv.index("--out") + 1]
    record = {"n_devices": N_DEVICES, "rc": 0, "ok": False,
              "skipped": False, "tail": ""}
    try:
        record["extras"] = run()
        record["ok"] = True
    except Exception as e:  # recorded, then re-raised for the CI log
        record["rc"] = 1
        record["tail"] = str(e)[:400]
        if out_path:
            with open(out_path, "w") as f:
                json.dump(record, f, indent=2)
        raise
    if out_path:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
        print("recorded -> %s" % out_path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
