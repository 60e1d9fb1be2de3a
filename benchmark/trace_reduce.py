"""From a ``jax.profiler`` capture (``*.xplane.pb``) to numbers: device
busy time, time per XLA module, the operations that took most time, and
the idle gaps with what the host was doing in them.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a TPU
capture of this program looks like (looked at by hand on the v5e, PR 22;
``fixtures/`` holds a cut of that capture and ``tests/benchmark`` pins
these functions on it):

* one plane per chip, ``/device:TPU:<n>``.  Its line ``XLA Modules`` has
  one event per execution of a jitted program, named
  ``jit_<function>(<fingerprint>)``; its line ``XLA Ops`` has one event
  per HLO operation executed, nested where an operation (a ``while``)
  runs others.  Times are device times already placed on the host's
  clock by the profiler.
* ``/host:CPU`` has one line per host thread; ``TraceAnnotation`` spans
  (the program's ``fabtpu.verify_dispatch`` / ``fabtpu.stage2_dispatch``
  and the harness's ``bench.*``) are events there, on the same clock.

All functions below work on plain tuples so that tests can feed them
made-up timelines as well.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

from benchmark.spans import overlap_len, union
from benchmark.timeline import median

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTANCE = re.compile(r"(\.\d+)+$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
#: host annotations worth keeping (everything else on the host plane is
#: the runtime's own bookkeeping)
HOST_PREFIXES = ("bench.", "fabtpu.")


@dataclasses.dataclass
class DeviceTrace:
    """Times in seconds on the profiler's clock.  ``modules`` and ``ops``
    hold ``(device, name, start, duration)``; ``host`` maps an annotation's
    name to its ``(start, duration)`` list."""

    modules: list
    ops: list
    host: dict

    @property
    def devices(self) -> list:
        return sorted({d for d, *_ in self.ops} | {d for d, *_ in self.modules})


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def module_name(event_name: str) -> str:
    """``jit_stage2(1234)`` → ``jit_stage2``."""
    return event_name.split("(", 1)[0]


def op_kind(event_name: str) -> str:
    """An operation's event carries its whole HLO text,
    ``%multiply_reduce_fusion.213 = (f32[6,3072]{...}, ...) fusion(...)``;
    its kind is its name without the instance number:
    ``multiply_reduce_fusion``."""
    return _INSTANCE.sub("", event_name.split(" = ", 1)[0].lstrip("%"))


def read(path: str) -> DeviceTrace:
    from jax.profiler import ProfileData

    modules, ops, host = [], [], {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                into = {MODULES_LINE: modules, OPS_LINE: ops}.get(line.name)
                if into is None:
                    continue
                for ev in line.events:
                    into.append((dev, ev.name, ev.start_ns * 1e-9,
                                 ev.duration_ns * 1e-9))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        host.setdefault(ev.name, []).append(
                            (ev.start_ns * 1e-9, ev.duration_ns * 1e-9))
    modules.sort(key=lambda e: e[2])
    # an operation that contains others starts with the first of them:
    # the longer one first
    ops.sort(key=lambda e: (e[2], -e[3]))
    for evs in host.values():
        evs.sort()
    return DeviceTrace(modules, ops, host)


def busy_intervals(trace: DeviceTrace, device: int, t0: float,
                   t1: float) -> list:
    """The disjoint intervals inside [t0, t1] in which an operation ran
    on ``device``."""
    return union([(max(s, t0), min(s + d, t1))
                  for dev, _n, s, d in trace.ops if dev == device])


def busy_seconds(trace: DeviceTrace, t0: float, t1: float) -> float:
    """Seconds in which an operation ran, averaged over the devices that
    ran any."""
    devs = trace.devices
    if not devs:
        return 0.0
    return sum(sum(b - a for a, b in busy_intervals(trace, d, t0, t1))
               for d in devs) / len(devs)


def module_runs(trace: DeviceTrace, name: str, t0: float, t1: float) -> list:
    """Durations (s) of the executions of XLA module ``name`` that began
    inside [t0, t1]."""
    return [d for _dev, n, s, d in trace.modules
            if module_name(n) == name and t0 <= s <= t1]


def median_run_ms(obs, module: str):
    """For a reader: the median device time (ms) of the executions of
    ``module`` that began inside the run's captured window; None without
    a capture or an execution."""
    if obs.device_trace is None:
        return None
    t0, t1, _offset = obs.capture_window
    runs = module_runs(obs.device_trace, module, t0, t1)
    return median(runs) * 1000.0 if runs else None


def top_ops(trace: DeviceTrace, t0: float, t1: float, n: int = 10) -> list:
    """``[["<module>/<kind of op>", seconds], ...]``: the kinds of
    operation that took most device time inside [t0, t1], each under the
    module that was running when it began.  An operation that contains
    others (the ladder's ``while``) counts its own time alone."""
    mods = [(dev, s, s + d, module_name(nm)) for dev, nm, s, d in trace.modules]
    total: dict = {}
    for dev in trace.devices:
        evs = [(s, s + d, nm) for dv, nm, s, d in trace.ops
               if dv == dev and s >= t0 and s + d <= t1]
        mine = [m for m in mods if m[0] == dev]
        mi = 0
        for i, (s, e, nm) in enumerate(evs):
            # self time: what the events nested in this one do not cover
            inner, j = [], i + 1
            while j < len(evs) and evs[j][0] < e:
                inner.append((evs[j][0], min(evs[j][1], e)))
                j += 1
            own = (e - s) - overlap_len(union(inner), [(s, e)])
            while mi + 1 < len(mine) and mine[mi][2] <= s:
                mi += 1
            mod = (mine[mi][3] if mine and mine[mi][1] <= s < mine[mi][2]
                   else "?")
            key = f"{mod}/{op_kind(nm)}"
            total[key] = total.get(key, 0.0) + own
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: DeviceTrace, t0: float, t1: float) -> list:
    """The intervals inside [t0, t1] in which no operation ran on the
    first device that ran any."""
    devs = trace.devices
    if not devs:
        return [(t0, t1)]
    gaps, at = [], t0
    for a, b in busy_intervals(trace, devs[0], t0, t1):
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if at < t1:
        gaps.append((at, t1))
    return gaps


def attribute_gaps(gaps, host_intervals: dict, n: int = 10) -> list:
    """``[[what the host was doing, idle seconds], ...]``: for each label
    of ``host_intervals`` (label → disjoint sorted intervals on the same
    clock as ``gaps``), the idle time during which it ran, and under
    ``(no span)`` the idle time during which none did.  Threads run side
    by side, so the entries can add up to more than the idle time."""
    gaps = union(gaps)
    out = {label: overlap_len(gaps, ivals)
           for label, ivals in host_intervals.items()}
    covered = union([iv for ivals in host_intervals.values() for iv in ivals])
    idle = sum(b - a for a, b in gaps)
    out["(no span)"] = idle - overlap_len(gaps, covered)
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])
            if v > 0][:n]
