"""Device-lane degradation guard: bounded retry, CPU fallback latch,
recovery probing.

Before this module every failure on the device verify lane was
happy-path: a TPU launch raising tore the whole deliver stream down,
and the CPU ``ops/p256v3.verify_host`` path existed but nothing ever
routed to it.  :class:`DeviceLaneGuard` is the state machine that
makes the lane survivable, shared by ``BlockValidator`` and the
crypto-free toy validators the chaos tests drive:

* **bounded retry** — a failed device launch retries up to ``retries``
  times with capped exponential backoff + jitter
  (``utils.backoff.Backoff``), each retry counted on
  ``device_verify_retries_total``;
* **degraded latch** — after ``fail_threshold`` CONSECUTIVE failed
  attempts the guard latches degraded: blocks route to the caller's
  CPU fallback (``ops/p256v3.verify_host`` + the host MVCC path in the
  real validator — correctness identical, the channel stays live),
  counted on ``fallback_blocks_total``, with the
  ``validator_degraded`` gauge at 1 and the state surfaced on
  ``/healthz``;
* **recovery probe** — every ``recovery_s`` a degraded guard risks ONE
  block on the device lane; a completed device verify re-arms the lane
  (gauge back to 0).  A failed probe costs that block a CPU re-verify,
  nothing more;
* **deadline** — with ``deadline_ms`` > 0, a device attempt (launch,
  or the fetch-side sync the validator reports via
  :meth:`check_deadline`) that takes longer counts as a failure toward
  the latch.  The result is still USED — a blocked XLA sync cannot be
  preempted from Python — so the deadline is a latch signal for future
  blocks, not a per-block abort; that is the honest contract and it is
  documented on the knob.

Every device attempt passes through the ``validator.verify_launch``
fault-injection point (fabric_tpu.faults), so a seeded FaultPlan
exercises exactly this machinery; fallback runs under
``faults.shield()`` — the recovery path must not be chased by the
fault that provoked it.

``fail_threshold=0`` (the default everywhere) disables the guard
entirely: callers skip construction and keep today's raise-through
behavior, so CPU-only hosts and tier-1 pay nothing.
"""

from __future__ import annotations

import logging
import random
import threading
import time

from fabric_tpu import faults
from fabric_tpu.utils.backoff import Backoff

_log = logging.getLogger("fabric_tpu.validator.degrade")

LAUNCH_POINT = "validator.verify_launch"


class DeviceLaneGuard:
    """See module docstring.  The latch state is LOCKED: launches
    record failures on the prefetch thread while fetch-side accounting
    (``_GuardedHandle``, ``validate_finish``'s deadline/success path)
    runs on the caller thread — the counter/latch transitions must not
    race.  The lock guards only the few scalar updates, never the
    launch or fallback work itself."""

    def __init__(self, retries: int = 2, fail_threshold: int = 3,
                 recovery_s: float = 30.0, deadline_ms: float = 0.0,
                 backoff: Backoff | None = None, clock=time.monotonic,
                 sleep=time.sleep, channel: str = "", registry=None,
                 rng: random.Random | None = None):
        if fail_threshold <= 0:
            raise ValueError(
                "DeviceLaneGuard needs fail_threshold >= 1 "
                "(0 disables the guard — don't construct one)"
            )
        self.retries = max(0, int(retries))
        self.fail_threshold = int(fail_threshold)
        self.recovery_s = float(recovery_s)
        self.deadline_ms = float(deadline_ms)
        self.channel = channel
        self._clock = clock
        self._sleep = sleep
        self._backoff = backoff or Backoff(
            base=0.05, cap=2.0, jitter=0.5, rng=rng
        )
        self._lock = threading.Lock()
        self._consecutive = 0
        self._degraded = False
        self._degraded_at = 0.0
        self._degraded_accum_s = 0.0
        self._last_probe = 0.0
        if registry is None:
            from fabric_tpu.ops_metrics import global_registry

            registry = global_registry()
        self._gauge = registry.gauge(
            "validator_degraded",
            "1 while the device verify lane is latched to CPU fallback",
        )
        self._retries_ctr = registry.counter(
            "device_verify_retries_total",
            "device verify attempts retried after a failure",
        )
        self._fallback_ctr = registry.counter(
            "fallback_blocks_total",
            "blocks routed through the CPU verify fallback",
        )
        self._gauge.set(0, channel=self.channel)

    # -- state ------------------------------------------------------------

    @property
    def degraded(self) -> bool:
        return self._degraded

    @property
    def consecutive_failures(self) -> int:
        return self._consecutive

    def degraded_seconds(self) -> float:
        """Total wall-clock spent degraded (bench chaos extras)."""
        with self._lock:
            live = (
                self._clock() - self._degraded_at if self._degraded
                else 0.0
            )
            return self._degraded_accum_s + live

    def record_failure(self, err: BaseException | None = None) -> None:
        with self._lock:
            self._consecutive += 1
            latched = (
                not self._degraded
                and self._consecutive >= self.fail_threshold
            )
            if latched:
                self._degraded = True
                self._degraded_at = self._clock()
                self._last_probe = self._degraded_at
                n = self._consecutive
        if latched:
            self._gauge.set(1, channel=self.channel)
            _log.warning(
                "%s: device verify lane DEGRADED after %d consecutive "
                "failures (%s) — routing blocks through the CPU "
                "fallback; recovery probe every %.1fs",
                self.channel or "validator", n, err, self.recovery_s,
            )
            # incident edge: the latch is exactly the moment the
            # flight-data recorder should freeze the trailing story
            # (import inside the rare branch — the unarmed fast path
            # never pays it)
            from fabric_tpu.observe import blackbox

            blackbox.notify(
                "degrade_latch", channel=self.channel,
                consecutive_failures=n,
                error=str(err) if err is not None else None,
            )

    def record_success(self) -> None:
        with self._lock:
            self._consecutive = 0
            self._backoff.reset()
            rearmed = self._degraded
            if rearmed:
                now = self._clock()
                down_s = now - self._degraded_at
                self._degraded_accum_s += down_s
                self._degraded = False
        if rearmed:
            self._gauge.set(0, channel=self.channel)
            _log.warning(
                "%s: device verify lane RECOVERED after %.1fs degraded",
                self.channel or "validator", down_s,
            )

    def should_probe(self) -> bool:
        """Degraded and due for a device-lane attempt."""
        with self._lock:
            return (
                self._degraded
                and self._clock() - self._last_probe >= self.recovery_s
            )

    def check_deadline(self, elapsed_s: float) -> bool:
        """Report a device-side duration (launch or fetch sync).  Over
        the deadline it counts as a lane failure (latch signal); the
        caller still uses the result.  Returns True when the deadline
        was exceeded."""
        if self.deadline_ms > 0 and elapsed_s * 1000.0 > self.deadline_ms:
            _log.warning(
                "%s: device verify took %.1fms (deadline %.1fms) — "
                "counting toward the degraded latch",
                self.channel or "validator", elapsed_s * 1000.0,
                self.deadline_ms,
            )
            self.record_failure()
            return True
        return False

    # -- the launch wrapper ------------------------------------------------

    def run_launch(self, launch_fn, fallback_fn, eager: bool = False,
                   fallback_count: int = 1):
        """Run ``launch_fn`` on the device lane with bounded retries,
        or route to ``fallback_fn`` (the CPU path) when degraded /
        exhausted.

        ``eager=True``: ``launch_fn`` completes the verify synchronously
        (toy validators), so success is recorded on return.  With the
        default ``eager=False`` the launch is an ASYNC dispatch — the
        caller records success/failure when the device actually syncs
        (``record_success`` / ``record_failure`` at fetch).

        ``fallback_count``: blocks the fallback covers (a coalesced
        group routes several blocks through one CPU re-verify) — feeds
        ``fallback_blocks_total``.
        """
        if self._degraded:
            if not self.should_probe():
                return self._fallback(fallback_fn, fallback_count)
            # recovery probe: risk ONE attempt, no retries — a failure
            # costs this block a CPU re-verify, nothing more
            with self._lock:
                self._last_probe = self._clock()
            try:
                faults.fire(LAUNCH_POINT, probe=True)
                t0 = self._clock()
                out = launch_fn()
            except Exception as e:
                _log.info(
                    "%s: device recovery probe failed (%s); staying "
                    "degraded", self.channel or "validator", e,
                )
                return self._fallback(fallback_fn, fallback_count)
            if eager and not self.check_deadline(self._clock() - t0):
                self.record_success()
            return out

        attempts = self.retries + 1
        last_err: BaseException | None = None
        for i in range(attempts):
            try:
                faults.fire(LAUNCH_POINT)
                t0 = self._clock()
                out = launch_fn()
            except Exception as e:
                last_err = e
                self.record_failure(e)
                if self._degraded or i == attempts - 1:
                    break
                self._retries_ctr.add(1, channel=self.channel)
                self._sleep(self._backoff.next())
                continue
            if eager and not self.check_deadline(self._clock() - t0):
                self.record_success()
            return out
        _log.warning(
            "%s: device verify launch failed %d attempt(s) (%s) — "
            "routing this block through the CPU fallback",
            self.channel or "validator", self._consecutive, last_err,
        )
        return self._fallback(fallback_fn, fallback_count)

    def count_fallback(self, count: int = 1) -> None:
        """Count blocks that rode the CPU lane OUTSIDE ``run_launch``
        (fetch-side re-verifies) — ``fallback_blocks_total`` must
        cover every CPU-verified block, not just launch-time routing."""
        self._fallback_ctr.add(count, channel=self.channel)

    def _fallback(self, fallback_fn, count: int = 1):
        self._fallback_ctr.add(count, channel=self.channel)
        # the recovery path must not be chased by the injected fault
        # that provoked it (a real dead TPU does not break the CPU)
        with faults.shield():
            return fallback_fn()
