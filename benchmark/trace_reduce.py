"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to the numbers
the benchmark reports: per device the busy union and idle share, time
per program and per op, and the longest idle gaps labelled by what the
host was doing. Reads the file with ``jax.profiler.ProfileData`` and
nothing else.

Planes: one per device (``/device:TPU:<n>``) and the host's
(``/host:CPU``, one line per thread). On a device plane the line
``XLA Modules`` holds one event per executed program (``jit_<fn>(<id>)``)
and ``XLA Ops`` one per HLO op. Times are nanoseconds from the start of
the trace on one clock for all planes.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
# host events that say nothing about what the host was doing
HOST_NOISE = re.compile(r"^(\$|ThreadpoolListener::)")

Interval = Tuple[float, float]


def newest_xplane(trace_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def merged(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no (merged) busy interval covers."""
    out, at = [], window[0]
    for s, e in busy:
        if s > at:
            out.append((at, min(s, window[1])))
        at = max(at, e)
        if at >= window[1]:
            break
    if at < window[1]:
        out.append((at, window[1]))
    return [(s, e) for s, e in out if e > s]


def program_name(event_name: str) -> str:
    """``jit_step(1234567)`` -> ``jit_step``; an op's HLO text
    ``%sort.20 = s32[...] sort(...)`` -> ``sort.20``."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name.split("(", 1)[0]


def totals(events: Iterable[Tuple[str, float, float]]
           ) -> Dict[str, Tuple[int, float]]:
    """name -> (calls, seconds) over ``(name, start_ns, dur_ns)``."""
    out: Dict[str, List[float]] = {}
    for name, _s, d in events:
        t = out.setdefault(program_name(name), [0, 0.0])
        t[0] += 1
        t[1] += d / 1e9
    return {k: (int(v[0]), v[1]) for k, v in out.items()}


class DeviceTrace:
    """One device plane, reduced."""

    def __init__(self, name: str, modules, ops) -> None:
        self.name = name
        base = ops if ops else modules      # [(name, start_ns, dur_ns)]
        self.busy = merged((s, s + d) for _n, s, d in base)
        self.busy_s = sum(e - s for s, e in self.busy) / 1e9
        self.module_totals = totals(modules)
        self.op_totals = totals(ops)

    def seconds(self, line: str, pattern: str) -> Tuple[int, float]:
        """(calls, seconds) of the programs/ops whose name matches."""
        rx = re.compile(pattern)
        src = self.module_totals if line == MODULES_LINE else self.op_totals
        calls = sum(c for n, (c, _s) in src.items() if rx.search(n))
        secs = sum(s for n, (_c, s) in src.items() if rx.search(n))
        return calls, secs


class Trace:
    def __init__(self, devices: List[DeviceTrace],
                 host: List[Tuple[str, float, float]],
                 window: Interval) -> None:
        self.devices = devices
        self.host = host            # [(name, start_ns, dur_ns)] all threads
        self.window = window        # ns
        self.window_s = (window[1] - window[0]) / 1e9

    def busiest(self) -> Optional[DeviceTrace]:
        return max(self.devices, key=lambda d: d.busy_s, default=None)

    def mean_busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(d.busy_s for d in self.devices) / len(self.devices)

    def idle_share(self, dev: DeviceTrace) -> float:
        return 1.0 - dev.busy_s / self.window_s

    def labelled_gaps(self, dev: DeviceTrace, longest: int = 200
                      ) -> List[Tuple[str, float]]:
        """Idle seconds on ``dev`` by what the host was doing: each of
        the ``longest`` gaps takes the name of the host event (JAX's own:
        ``PjitFunction``, ``shard_args``, ``XlaLinearize`` ...) that
        covers most of it, ``host.untraced`` where none covers a fifth —
        the job's own Python, which has no spans yet; the shorter gaps
        together are ``short gaps``."""
        gs = sorted(gaps(dev.busy, self.window),
                    key=lambda g: g[0] - g[1])
        by: Dict[str, float] = {}
        considered = gs[:longest]
        # a label needs to cover a fifth of its gap: shorter host events
        # than a fifth of the shortest gap considered cannot label any
        floor = 0.2 * min((e - s for s, e in considered), default=0.0)
        host = sorted((s, s + d, n) for n, s, d in self.host
                      if d >= floor and d > 0 and not HOST_NOISE.match(n))
        starts = [h[0] for h in host]
        for s, e in considered:
            best, best_cover = "host.untraced", 0.2 * (e - s)
            hi = bisect.bisect_left(starts, e)
            for hs, he, name in host[:hi]:
                if he <= s:
                    continue
                cover = min(e, he) - max(s, hs)
                if cover > best_cover:
                    best, best_cover = program_name(name), cover
            by[best] = by.get(best, 0.0) + (e - s) / 1e9
        rest = sum(e - s for s, e in gs[longest:]) / 1e9
        if rest:
            by["short gaps"] = rest
        return sorted(by.items(), key=lambda kv: -kv[1])

    def breakdown(self) -> dict:
        dev = self.busiest()
        if dev is None:
            return {"device_ops": [], "idle_gaps": []}
        ops = sorted(((n, s) for n, (_c, s) in dev.op_totals.items()),
                     key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in
                              self.labelled_gaps(dev)[:10]]}


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def reduce_profile(profile) -> Trace:
    """``jax.profiler.ProfileData`` -> :class:`Trace`."""
    devices, host = [], []
    lo, hi = None, None
    for plane in profile.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if DEVICE_PLANE.match(plane.name):
            mods = _events(lines[MODULES_LINE]) if MODULES_LINE in lines \
                else []
            ops = _events(lines[OPS_LINE]) if OPS_LINE in lines else []
            devices.append(DeviceTrace(plane.name, mods, ops))
            evs = mods + ops
        elif plane.name.startswith("/host:CPU"):
            evs = [ev for ln in plane.lines for ev in _events(ln)]
            host.extend(evs)
        else:
            continue
        for _n, s, d in evs:
            lo = s if lo is None else min(lo, s)
            hi = s + d if hi is None else max(hi, s + d)
    devices.sort(key=lambda d: d.name)
    return Trace(devices, host, (lo or 0.0, hi or 0.0))


def reduce_file(path: str) -> Trace:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))
