"""Process-level XLA environment: flags, the compile cache, and the
device a process owns.

The flag helpers are import-light on purpose (no jax import): callers
must apply them BEFORE jax initializes its backends (tests/conftest.py,
__graft_entry__.py).

One device-owning process per chip: a TPU belongs to the first process
that initializes the backend, so exactly one process per chip calls
:func:`claim_device` (``chip_smoke.py``, the CLI's ``peer`` without a
``sidecar_endpoint``, ``sidecar-serve``); every other peer on the host
attaches through ``sidecar_endpoint`` and pins itself to the CPU
backend with :func:`pin_cpu_backend`.
"""

from __future__ import annotations

import logging
import os

_log = logging.getLogger("fabric_tpu.xla_env")

#: fabric_tpu/utils/xla_env.py → the checkout root, normalised: the
#: cache directory is part of every cache key, so each caller must
#: spell it identically
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cpu_named_explicitly() -> bool:
    """True when ``JAX_PLATFORMS`` asks for the CPU backend by name —
    the tests and the CPU recipes do; a process that merely FOUND no
    accelerator does not."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def ensure_cpu_compile_workaround() -> None:
    """Disable the jax 0.9 CPU fusion emitters.

    They blow up superlinearly on the deep uint32 dependency chains of
    the crypto kernels (a 64-round SHA-256 compression never finishes
    compiling on a small host); the legacy emitter compiles it in ~2s.

    Applied only where ``JAX_PLATFORMS`` names ``cpu``: XLA ABORTS the
    whole process on a flag it does not know at backend init, and a
    process that opens the TPU hands ``XLA_FLAGS`` to the TPU runtime
    as well — a CPU-compiler flag has no business there.
    """
    if not _cpu_named_explicitly():
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_cpu_use_fusion_emitters" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_cpu_use_fusion_emitters=false"
        ).strip()


def ensure_host_device_count(n: int) -> None:
    """Request ``n`` virtual host-platform devices (no-op if any count
    is already configured)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def enable_compile_cache() -> str:
    """Arm jax's persistent compile cache so every process that
    validates (bench rounds, the peer daemon, the sidecar server, the
    tests) reuses one set of compiled verify graphs — a restart must
    re-attach in seconds, not re-compile the ladder on its first block.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, the operator placed the
    cache: jax reads the variable itself and NO directory is set in
    code.  Otherwise the cache lives at ``<checkout>/.jax_cache``.
    Returns the directory in use."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(_CHECKOUT, ".jax_cache"),
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


def pin_cpu_backend() -> None:
    """Keep this process off the accelerator: a peer attached to a
    validation sidecar still jits its MVCC kernel and its local
    fallback, and on a chip host those must not contend with the
    sidecar for the one chip.  Call before any backend initializes."""
    import jax

    jax.config.update("jax_platforms", "cpu")


def on_accelerator() -> bool:
    """Whether this process's default backend is an accelerator.  What
    is only worth doing where a chip is the device lane asks here: a
    channel's warm-up (``peer/node.py``) lowers a dozen programs, tens
    of seconds each on any backend, and on the CPU backend (tests, dev
    networks, a peer attached to a sidecar) no deployment waits for
    them while a test network opens channels by the dozen."""
    import jax

    return jax.default_backend() != "cpu"


def claim_device(who: str) -> dict:
    """Initialize the backend for a device-owning process and say what
    it runs on: → ``{"platform", "kind", "count"}`` as jax reports
    them.  Raises when the backend is ``cpu`` and ``JAX_PLATFORMS`` did
    not name ``cpu`` — a process that found no accelerator must not
    serve device metric names off the CPU backend."""
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    _log.info("%s: platform=%s device_kind=%s devices=%d", who,
              info["platform"], info["kind"], info["count"])
    if info["platform"] == "cpu" and not _cpu_named_explicitly():
        raise RuntimeError(
            f"{who}: jax found no accelerator and fell back to the cpu "
            "backend; set JAX_PLATFORMS=cpu to run on the CPU on purpose"
        )
    return info
