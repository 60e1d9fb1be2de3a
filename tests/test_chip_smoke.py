"""``chip_smoke.py`` must not rot between chip runs: phases 0–2 at a
tiny size on the CPU through ``run``'s arguments, and the script itself
must refuse the CPU."""

import os
import subprocess
import sys

import chip_smoke
from fabric_tpu.utils.xla_env import claim_device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = chip_smoke.Size(
    n_tx=20, n_blocks=4, preload_keys=2000, hot_keys=16,
    served_waves=(4,), served_deadline_s=300.0,
)


def test_phases_run_on_cpu_at_tiny_size(tmp_path):
    device = claim_device("test_chip_smoke")
    result = chip_smoke.run(TINY, seed=7, device=device,
                            workdir=str(tmp_path))
    p1, p2 = result["phase1"], result["phase2"]
    assert p1["height"] == TINY.n_blocks
    assert {c["kernel"] for c in p1["compiled"]} == {"verify", "stage2"}
    assert p2["txs"] == sum(TINY.served_waves) and p2["blocks"] >= 1
    assert not any(p1["counters"].values())
    assert not any(p2["counters"].values())


def test_script_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode != 0
    assert "'cpu'" in res.stderr
    assert '"ok"' not in res.stdout
