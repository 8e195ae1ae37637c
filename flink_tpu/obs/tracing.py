"""Tracing: spans for checkpoint/recovery, the data path's phase clock,
and on-demand thread sampling.

ref: SURVEY §6.1 — flink-core ``traces/`` Span/TraceReporter (emitted
for checkpointing and job recovery from CheckpointStatsTracker), and
the REST-triggered flame graphs of runtime/webmonitor/threadinfo.
Latency markers (the third §6.1 mechanism) already ride the driver's
emit-latency histogram; this module adds the other two.

Design: a process-global ``Tracer`` with a bounded ring of completed
spans. Spans are cheap (one dataclass + two clock reads) and the ring
is lock-guarded but uncontended — span starts/ends happen on the
driver loop and checkpoint threads at human frequencies, never per
record. The REST server exposes the ring at /traces and aggregated
thread stacks at /flamegraph.

The data path has a ``PhaseClock`` per run (the driver's; a bare
operator has its own): per thread at most ONE phase is open, so a
thread's time is a flat partition into named leaves — seconds, count
and longest single interval each. Tracer spans and phases alike hold a
``jax.profiler.TraceAnnotation`` open for their interval: whenever a
profiler session runs (``pipeline.profile-dir``, ``jax.profiler
.start_trace``) they are host events of ITS trace, on the device
trace's clock. There is no switch: with no session a span is one
object and two calls. Phases are per batch or per fire, never per
record.

One level below a leaf: ``PhaseClock.detail(name)`` times a stretch of
the open leaf WITHOUT switching the phase (seconds, count, longest,
read by ``details()``; a host event ``<leaf>/<name>`` nested in the
leaf's). With no leaf open, as on a thread that waits between its
spans, a detail is a counter only.
"""
from __future__ import annotations

import collections
import dataclasses
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["PhaseClock", "Span", "Tracer", "tracer", "sample_threads"]


def _annotation(name: str, attributes: Dict[str, Any]):
    """An ENTERED ``jax.profiler.TraceAnnotation``: recorded by the
    profiler itself when a session is running, inert otherwise."""
    ann = TraceAnnotation(name, **attributes)
    ann.__enter__()
    return ann


class _ThreadPhases:
    """One thread's side of a PhaseClock: the open phase and this
    thread's totals (single writer; ``PhaseClock.snapshot`` merges)."""

    __slots__ = ("name", "t0", "ann", "stats", "detail", "details")

    def __init__(self) -> None:
        self.name: Optional[str] = None
        self.t0 = 0.0
        self.ann = None
        # name -> [seconds, count, longest_s, longest_began]
        self.stats: Dict[str, List[float]] = {}
        # the open detail, and key -> [seconds, count, longest_s]
        self.detail: Optional["_Detail"] = None
        self.details: Dict[str, List[float]] = {}


class _PhaseSpan:
    """``with clock.span(name) as sp:`` — ``name`` for the block, then
    the phase that was open before it again (none, off the loop thread).
    ``sp.t0`` / ``sp.t1`` are the clock readings at its ends."""

    __slots__ = ("_clock", "_name", "_attrs", "_prev", "t0", "t1")

    def __init__(self, clock: "PhaseClock", name: str, attrs) -> None:
        self._clock, self._name, self._attrs = clock, name, attrs
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "_PhaseSpan":
        self._prev, _, self.t0 = self._clock._switch(self._name, self._attrs)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.t1 = self._clock._switch(self._prev, {})[2]

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class _Detail:
    """``with clock.detail(name):`` — a stretch of the leaf open on the
    calling thread (``leaf``; None where none is), under the key
    ``<leaf>/<name>``. Its clock runs only while that leaf is the open
    phase: a phase the block switches to is that phase's time, and the
    detail goes on when its leaf is open again."""

    __slots__ = ("_tp", "_name", "leaf", "key", "t0", "ann", "acc")

    def __init__(self, tp: _ThreadPhases, name: str) -> None:
        self._tp, self._name = tp, name

    def __enter__(self) -> "_Detail":
        tp = self._tp
        if tp.detail is not None:
            raise RuntimeError(
                f"detail {self._name!r} opened inside {tp.detail.key!r}: "
                "there is one level below a leaf")
        self.leaf = tp.name
        self.key = (self._name if self.leaf is None
                    else f"{self.leaf}/{self._name}")
        self.acc = 0.0
        tp.detail = self
        self.resume()
        return self

    def resume(self) -> None:
        # a thread that waits with no leaf open must not put its name on
        # the device's idle gaps: no host event there
        self.ann = (None if self.leaf is None
                    else _annotation(self.key, {}))
        self.t0 = time.perf_counter()

    def pause(self, now: float) -> None:
        self.acc += now - self.t0
        self.t0 = None
        if self.ann is not None:
            self.ann.__exit__(None, None, None)

    def __exit__(self, exc_type, exc, tb) -> None:
        if self.t0 is not None:
            self.pause(time.perf_counter())
        tp, acc = self._tp, self.acc
        tp.detail = None
        st = tp.details.get(self.key)
        if st is None:
            tp.details[self.key] = [acc, 1, acc]
        else:
            st[0] += acc
            st[1] += 1
            if acc > st[2]:
                st[2] = acc


class PhaseClock:
    """Where each thread of one run spends its wall time.

    ``phase(name)`` closes the phase open on the CALLING thread and
    opens ``name`` (and does nothing when ``name`` is the open one);
    ``stop()`` closes it and opens none; ``span(name)``
    is ``name`` for a ``with`` block and the previous phase after it. No
    phase encloses another, so a thread's phases sum to its wall time
    between its first ``phase()`` and its ``stop()``. ``detail(name)``
    times a ``with`` block as a child of the open leaf and leaves the
    partition alone: a leaf's self time is its seconds less its
    details'."""

    def __init__(self) -> None:
        self.t_start = time.perf_counter()
        self._local = threading.local()
        self._threads: List[_ThreadPhases] = []
        self._lock = threading.Lock()

    def _mine(self) -> _ThreadPhases:
        try:
            return self._local.phases
        except AttributeError:
            tp = self._local.phases = _ThreadPhases()
            with self._lock:
                self._threads.append(tp)
            return tp

    def _switch(self, name: Optional[str], attrs: Dict[str, Any]):
        """-> (the phase that was open, when it opened, now)."""
        tp = self._mine()
        now = time.perf_counter()
        prev, t_open = tp.name, tp.t0
        if prev == name and not attrs:
            return prev, t_open, now    # already open: one interval
        detail = tp.detail
        if detail is not None and detail.t0 is not None:
            detail.pause(now)           # a child ends before its leaf
        if prev is not None:
            tp.ann.__exit__(None, None, None)
            dt = now - t_open
            st = tp.stats.get(prev)
            if st is None:
                tp.stats[prev] = [dt, 1, dt, t_open]
            else:
                st[0] += dt
                st[1] += 1
                if dt > st[2]:
                    st[2], st[3] = dt, t_open
        tp.name, tp.t0 = name, now
        tp.ann = None if name is None else _annotation(name, attrs)
        if detail is not None and detail.leaf == name:
            detail.resume()
        return prev, t_open, now

    def phase(self, name: str, **attributes: Any) -> float:
        """Switch the calling thread to ``name``; returns the clock
        reading (``time.perf_counter()``) of the switch."""
        return self._switch(name, attributes)[2]

    def stop(self) -> float:
        """Close the calling thread's open phase, if any; returns the
        clock reading, as ``phase`` does."""
        return self._switch(None, {})[2]

    def span(self, name: str, **attributes: Any) -> _PhaseSpan:
        return _PhaseSpan(self, name, attributes)

    def detail(self, name: str) -> _Detail:
        return _Detail(self._mine(), name)

    def open_phase(self) -> Optional[str]:
        """The phase open on the calling thread."""
        return self._mine().name

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """name -> ``seconds``, ``count``, ``longest_ms`` and
        ``longest_at_s`` (when that interval began, seconds after the
        clock was made) over all threads' CLOSED intervals."""
        with self._lock:
            threads = list(self._threads)
        out: Dict[str, Dict[str, float]] = {}
        for tp in threads:
            for name, (secs, n, longest, began) in list(tp.stats.items()):
                o = out.setdefault(name, {"seconds": 0.0, "count": 0,
                                          "longest_ms": 0.0,
                                          "longest_at_s": 0.0})
                o["seconds"] += secs
                o["count"] += n
                if longest * 1e3 > o["longest_ms"]:
                    o["longest_ms"] = longest * 1e3
                    o["longest_at_s"] = began - self.t_start
        return out

    def details(self) -> Dict[str, Dict[str, float]]:
        """``<leaf>/<name>`` (the name alone where no leaf was open) ->
        ``seconds``, ``count`` and ``longest_ms`` over all threads'
        CLOSED details."""
        with self._lock:
            threads = list(self._threads)
        out: Dict[str, Dict[str, float]] = {}
        for tp in threads:
            for key, (secs, n, longest) in list(tp.details.items()):
                o = out.setdefault(key, {"seconds": 0.0, "count": 0,
                                         "longest_ms": 0.0})
                o["seconds"] += secs
                o["count"] += n
                o["longest_ms"] = max(o["longest_ms"], longest * 1e3)
        return out


@dataclasses.dataclass
class Span:
    name: str
    start: float                  # wall clock, what /traces shows
    end: Optional[float] = None
    attributes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    duration_ms: Optional[float] = None   # on the monotonic clock

    def to_dict(self) -> Dict[str, Any]:
        return {"name": self.name, "start": self.start,
                "duration_ms": self.duration_ms,
                "attributes": dict(self.attributes)}


class _SpanHandle:
    """Context manager recording one span; ``set(k, v)`` attaches
    attributes mid-flight (e.g. bytes persisted)."""

    def __init__(self, trc: "Tracer", span: Span) -> None:
        self._trc = trc
        self.span = span
        self._ann = _annotation(span.name, span.attributes)
        self._t0 = time.perf_counter()

    def set(self, key: str, value: Any) -> "_SpanHandle":
        self.span.attributes[key] = value
        return self

    def __enter__(self) -> "_SpanHandle":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None:
            self.span.attributes["error"] = f"{type(exc).__name__}: {exc}"
        self.span.duration_ms = (time.perf_counter() - self._t0) * 1e3
        self._ann.__exit__(None, None, None)
        self._trc._finish(self.span)


class Tracer:
    def __init__(self, capacity: int = 512) -> None:
        self._done: collections.deque = collections.deque(maxlen=capacity)
        self._lock = threading.Lock()

    def span(self, name: str, **attributes: Any) -> _SpanHandle:
        return _SpanHandle(self, Span(name, time.time(),
                                      attributes=dict(attributes)))

    def _finish(self, span: Span) -> None:
        span.end = time.time()
        with self._lock:
            self._done.append(span)

    def spans(self, name_prefix: str = "") -> List[Dict[str, Any]]:
        with self._lock:
            items = list(self._done)
        return [s.to_dict() for s in items
                if s.name.startswith(name_prefix)]

    def clear(self) -> None:
        with self._lock:
            self._done.clear()


# process-global tracer (the metric-registry pattern: one per process,
# sub-systems attach by name)
tracer = Tracer()


def sample_threads(seconds: float = 1.0, hz: float = 50.0) -> Dict[str, Any]:
    """Aggregate stack samples across all live threads — the flame-graph
    data (ref: JobVertexFlameGraphHandler / ThreadInfoSample: REST-
    triggered sampling, aggregated frames). Returns {stack -> count}
    with stacks rendered innermost-last as ';'-joined frames, plus the
    sampling parameters (collapsed format: feed straight to any
    flamegraph renderer)."""
    interval = 1.0 / hz
    counts: Dict[str, int] = {}
    deadline = time.time() + seconds
    n = 0
    while time.time() < deadline:
        for tid, frame in sys._current_frames().items():
            if tid == threading.get_ident():
                continue  # the sampler itself is noise
            frames = []
            f = frame
            while f is not None:
                code = f.f_code
                frames.append(f"{code.co_name}@"
                              f"{code.co_filename.rsplit('/', 1)[-1]}:"
                              f"{f.f_lineno}")
                f = f.f_back
            stack = ";".join(reversed(frames))
            counts[stack] = counts.get(stack, 0) + 1
        n += 1
        time.sleep(interval)
    return {"samples": n, "seconds": seconds, "hz": hz, "stacks": counts}
