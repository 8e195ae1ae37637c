"""The benchmark's one load generator: a source, a sink, and what the
host was doing meanwhile.

A traffic mix is a data file (``benchmark/traffic/<mix>.json``): the
``kind`` of its schedule (``benchmark/traffic_kinds/<kind>.py``: event id
-> event-time timestamp), that kind's parameters, and ``paced``:
``false`` — the backlog is always there: the source hands over the next
batch as soon as the job asks (catch-up / replay); ``true`` — open loop:
an event stamped ``t`` ms is DUE at ``window open + t`` ms of wall time,
and a batch is released when its last event is due, whether or not the
job has kept up.

Record CONTENT belongs to the configuration (``configs/<module>.py``
``make_pool``): a pool of seeded batches, made once during set-up and
cycled, so that generating a batch inside the measured window costs one
timestamp vector and every seed does the same amount of work per batch.
"""
from __future__ import annotations

import gc
import os
import resource
import threading
import time
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from flink_tpu.api.sinks import FnSink
from flink_tpu.api.sources import Source


class BenchSource(Source):
    """One split of numbered batches: batch ``i`` carries the fields of
    pool batch ``i % len(pool)`` and the schedule's timestamps for event
    ids ``[i*n, (i+1)*n)``.

    ``max_batches``: stop after that many (warm-up passes).
    ``seconds``: stop offering once that much wall time has passed since
    the split was opened (the measured window); the batch in hand is
    still delivered, nothing after it.
    ``paced``: release each batch when its last event is due.
    """

    def __init__(self, pool: List[Dict[str, np.ndarray]], schedule,
                 batch_size: int, *, schema: Optional[Dict[str, str]] = None,
                 paced: bool = False, seconds: Optional[float] = None,
                 max_batches: Optional[int] = None) -> None:
        self.pool = pool
        self.schedule = schedule
        self.n = int(batch_size)
        self.schema = schema
        self.paced = bool(paced)
        self.seconds = seconds
        self.max_batches = max_batches
        # what the window saw; read after env.execute() returns
        self.t_open: Optional[float] = None
        self.batches = 0
        self.max_ts = -1
        self.gen_s: List[float] = []       # generation time per batch
        self.late_s: List[float] = []      # release - due (paced)
        self.release_s: List[float] = []   # release wall time per batch

    def declared_schema(self):
        return dict(self.schema) if self.schema else None

    def open_split(self, split: str, start_pos: int = 0
                   ) -> Iterator[Tuple[Dict[str, np.ndarray], np.ndarray]]:
        if start_pos:
            raise ValueError("the benchmark's source does not resume")
        self.t_open = time.perf_counter()
        deadline = (None if self.seconds is None
                    else self.t_open + self.seconds)
        i = 0
        while self.max_batches is None or i < self.max_batches:
            t0 = time.perf_counter()
            if deadline is not None and t0 >= deadline:
                return
            ts = self.schedule.batch_ts(i, self.n)
            data = self.pool[i % len(self.pool)]
            last = int(ts[-1])
            t1 = time.perf_counter()
            if self.paced:
                due = self.t_open + last / 1e3
                if deadline is not None and due >= deadline:
                    return
                _sleep_until(due)
                now = time.perf_counter()
                self.late_s.append(now - due)
            else:
                now = t1
            self.gen_s.append(t1 - t0)
            self.release_s.append(now)
            self.batches = i + 1
            self.max_ts = last
            yield data, ts
            i += 1


def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        # sleep() overshoots by up to a scheduler quantum: stop short
        # and take the last half millisecond in small steps
        time.sleep(left - 0.0005 if left > 0.001 else 0.0001)


CPU_STAT_FILES = ("/sys/fs/cgroup/cpu.stat", "/sys/fs/cgroup/cpu/cpu.stat",
                  "/sys/fs/cgroup/cpu,cpuacct/cpu.stat")


def host_cpu_counters() -> Dict[str, float]:
    """What the machine says about this process's share of its CPUs: the
    container's quota counters (cgroup ``cpu.stat``: ``nr_throttled``,
    ``throttled_usec`` / ``throttled_time``), the kernel's CPU pressure
    total, the seconds the virtual machine's CPUs were taken by its host
    (``steal``, summed over CPUs), and this process's CPU seconds and
    context switches. A file that is not there leaves its keys out."""
    out: Dict[str, float] = {}
    for path in CPU_STAT_FILES:
        try:
            with open(path) as f:
                for line in f:
                    k, _, v = line.partition(" ")
                    out["cgroup." + k] = float(v)
            break
        except (OSError, ValueError):
            continue
    try:
        with open("/proc/pressure/cpu") as f:
            for line in f:
                kind, *rest = line.split()
                out[f"pressure.{kind}.total_us"] = float(
                    dict(x.split("=") for x in rest)["total"])
    except (OSError, ValueError, KeyError):
        pass
    try:
        with open("/proc/stat") as f:       # "cpu user nice system idle
            f0 = f.readline().split()       #  iowait irq softirq steal ..."
        out["vm.steal_s"] = float(f0[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        pass
    ru = resource.getrusage(resource.RUSAGE_SELF)
    out.update({"self.user_s": ru.ru_utime, "self.sys_s": ru.ru_stime,
                "self.switches_voluntary": float(ru.ru_nvcsw),
                "self.switches_forced": float(ru.ru_nivcsw)})
    return out


def counters_since(before: Dict[str, float]) -> Dict[str, float]:
    now = host_cpu_counters()
    return {k: round(now[k] - v, 6) for k, v in before.items() if k in now}


class Heartbeat:
    """A thread that only sleeps: the gaps between its wake-ups show when
    the whole process (not just the job) stood still — a starved machine,
    or something that holds the interpreter lock, such as a full
    collection by Python's garbage collector, which it times as well. At
    each gap it reads how long the container's CPU quota throttled the
    process since the gap before, so a stall can be put down to the quota
    or not. Runs in traced runs only: the end-to-end runs carry no thread
    of the benchmark's beside the source and the sink."""

    PERIOD_S = 0.005
    STALL_S = 0.02      # a gap this long is a stall, not scheduling jitter

    def __init__(self) -> None:
        # (wall time, seconds, [ms the quota throttled the container, ms
        # of CPU the host took from the machine] since the gap before)
        self.gaps: List[Tuple[float, float, list]] = []
        self.gc_long: List[Tuple[float, int, float]] = []  # (time, gen, s)
        self._gc_t0 = 0.0
        self._lost_ms = self._read_lost_ms()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    @staticmethod
    def _read_lost_ms() -> List[Optional[float]]:
        c = host_cpu_counters()
        thr = (c["cgroup.throttled_usec"] / 1e3
               if "cgroup.throttled_usec" in c
               else c["cgroup.throttled_time"] / 1e6   # cgroup v1: ns
               if "cgroup.throttled_time" in c else None)
        steal = c.get("vm.steal_s")
        return [thr, None if steal is None else 1e3 * steal]

    def __enter__(self) -> "Heartbeat":
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._gc_t0 = now
        elif now - self._gc_t0 >= self.STALL_S:
            self.gc_long.append((self._gc_t0, int(info["generation"]),
                                 now - self._gc_t0))

    def _run(self) -> None:
        last = time.perf_counter()
        while not self._stop.wait(self.PERIOD_S):
            now = time.perf_counter()
            if now - last >= self.STALL_S:
                lost = self._read_lost_ms()
                self.gaps.append((last, now - last, [
                    None if a is None or b is None else round(a - b, 1)
                    for a, b in zip(lost, self._lost_ms)]))
                self._lost_ms = lost
                now = time.perf_counter()
            last = now

    def stall_s(self) -> float:
        return sum(g for _t, g, _lost in self.gaps)

    def longest(self, t_open: float, n: int = 5) -> List[list]:
        """``[[seconds into the window, gap in ms, ms throttled by the
        quota, ms of CPU stolen by the host (all CPUs together; the last
        two since the gap before)], ...]``, longest first."""
        top = sorted(self.gaps, key=lambda g: -g[1])[:n]
        return [[round(t - t_open, 3), round(1e3 * g, 1), *lost]
                for t, g, lost in top]

    def gc_pauses(self, t_open: float, n: int = 5) -> List[List[float]]:
        """``[[seconds into the window, generation, ms], ...]``"""
        top = sorted(self.gc_long, key=lambda g: -g[2])[:n]
        return [[round(t - t_open, 3), gen, round(1e3 * s, 1)]
                for t, gen, s in top]


class RecordingSink:
    """Collects what the job commits, with the wall time each sink batch
    arrived (perf_counter): the latency clock's far end."""

    def __init__(self) -> None:
        self.batches: List[Dict[str, np.ndarray]] = []
        self.arrival_s: List[float] = []
        self._lock = threading.Lock()
        self.sink = FnSink(self._write)

    def _write(self, batch: Dict[str, np.ndarray]) -> None:
        now = time.perf_counter()
        kept = {k: np.array(v) for k, v in batch.items()}
        with self._lock:
            self.batches.append(kept)
            self.arrival_s.append(now)

    def last_arrival(self) -> Optional[float]:
        return self.arrival_s[-1] if self.arrival_s else None

    def first_arrival_by(self, field: str) -> Dict[int, float]:
        """value of ``field`` (a window end) -> wall time its first row
        arrived."""
        first: Dict[int, float] = {}
        for b, t in zip(self.batches, self.arrival_s):
            for v in np.unique(np.asarray(b[field])).tolist():
                first.setdefault(int(v), t)
        return first
