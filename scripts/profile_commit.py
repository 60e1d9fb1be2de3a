"""Phase breakdown of the north-star block-commit path (dev tool)."""
import sys, time
import numpy as np

sys.path.insert(0, ".")
import bench


def main(n_tx=1000):
    blocks, fresh_state, fresh_validator, mgr, prov, CC, _ninv = bench._build_commit_network(n_tx)
    blk = blocks[0]
    state = fresh_state()
    v = fresh_validator(state)

    # piecewise timings of validator.validate
    from fabric_tpu.ops import p256
    for rep in range(3):
        t0 = time.perf_counter()
        txs, items, _rwp = v._parse(blk)
        t1 = time.perf_counter()
        sig_valid = np.asarray(p256.verify_host(items), bool)
        t2 = time.perf_counter()
        flt, batch, hist = v.validate(blk)
        t3 = time.perf_counter()
        print(f"rep{rep}: parse={t1-t0:.3f}s verify={t2-t1:.3f}s full_validate={t3-t2:.3f}s n_items={len(items)}")

    import cProfile, pstats
    pr = cProfile.Profile()
    pr.enable()
    v.validate(blk)
    pr.disable()
    st = pstats.Stats(pr)
    st.sort_stats("cumulative").print_stats(25)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 1000)
