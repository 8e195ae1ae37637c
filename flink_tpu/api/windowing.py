"""Window assigners, windows, and triggers — the north-star API surface.

ref: streaming/api/windowing/assigners/{WindowAssigner,
TumblingEventTimeWindows,SlidingEventTimeWindows,EventTimeSessionWindows,
GlobalWindows}.java, windows/TimeWindow.java, triggers/{Trigger,
EventTimeTrigger,CountTrigger,PurgingTrigger}.java.

TPU-first redesign: time windows are **pane-decomposed** up front. The
reference's DataStream ``WindowOperator`` writes every element into each
overlapping window's state (a Q5 10s/1s sliding window costs 10 state
writes per element); the Table runtime's slicing optimization
(flink-table-runtime .../operators/window/ SliceAssigner) aggregates each
element once per non-overlapping slice and combines slices at fire time.
Here slicing is the *only* mode: an assigner exposes ``pane_ms`` (the
slice), every element is scatter-added into exactly one ``(key, pane)``
cell, and a window is a contiguous run of ``panes_per_window`` panes —
which is what makes the whole thing one dense tensor op on the MXU/VPU.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

from flink_tpu.records import MIN_TS


@dataclasses.dataclass(frozen=True, order=True)
class TimeWindow:
    """[start, end) window in epoch ms (ref: windows/TimeWindow.java)."""

    start: int
    end: int

    def max_timestamp(self) -> int:
        return self.end - 1

    def __repr__(self) -> str:
        return f"TimeWindow[{self.start}, {self.end})"


class WindowAssigner:
    """Base assigner. Pane-decomposable assigners (all time windows)
    report a pane length and window composition; session windows are
    merging and handled by the session registry instead.
    """

    is_event_time: bool = True
    is_merging: bool = False

    @property
    def pane_ms(self) -> int:
        raise NotImplementedError

    @property
    def size_ms(self) -> int:
        raise NotImplementedError

    @property
    def slide_ms(self) -> int:
        raise NotImplementedError

    @property
    def offset_ms(self) -> int:
        return 0

    @property
    def panes_per_window(self) -> int:
        return self.size_ms // self.pane_ms

    @property
    def panes_per_slide(self) -> int:
        return self.slide_ms // self.pane_ms

    def pane_index(self, timestamp: int) -> int:
        """Global pane id of a timestamp (device version lives in
        ops/window.py; both must agree)."""
        return (timestamp - self.offset_ms) // self.pane_ms

    def window_for_end_pane(self, end_pane: int) -> TimeWindow:
        end = end_pane * self.pane_ms + self.offset_ms
        return TimeWindow(end - self.size_ms, end)

    def assign_windows(self, timestamp: int) -> list[TimeWindow]:
        """Host/reference-semantics path (harness tests golden-check the
        device kernels against this; ref: WindowAssigner.assignWindows)."""
        if timestamp == MIN_TS:
            return []
        last_start = timestamp - (timestamp - self.offset_ms) % self.slide_ms
        out = []
        start = last_start
        while start > timestamp - self.size_ms:
            out.append(TimeWindow(start, start + self.size_ms))
            start -= self.slide_ms
        return list(reversed(out))


@dataclasses.dataclass(frozen=True)
class TumblingEventTimeWindows(WindowAssigner):
    """ref: assigners/TumblingEventTimeWindows.java"""

    size: int
    offset: int = 0

    @classmethod
    def of(cls, size_ms: int, offset_ms: int = 0) -> "TumblingEventTimeWindows":
        return cls(size_ms, offset_ms)

    @property
    def pane_ms(self) -> int:
        return self.size

    @property
    def size_ms(self) -> int:
        return self.size

    @property
    def slide_ms(self) -> int:
        return self.size

    @property
    def offset_ms(self) -> int:
        return self.offset


@dataclasses.dataclass(frozen=True)
class SlidingEventTimeWindows(WindowAssigner):
    """ref: assigners/SlidingEventTimeWindows.java — but lowered to panes
    (slices), NOT per-window state writes; see module docstring."""

    size: int
    slide: int
    offset: int = 0

    @classmethod
    def of(cls, size_ms: int, slide_ms: int, offset_ms: int = 0) -> "SlidingEventTimeWindows":
        return cls(size_ms, slide_ms, offset_ms)

    def __post_init__(self) -> None:
        if self.size <= 0 or self.slide <= 0:
            raise ValueError("size and slide must be positive")

    @property
    def pane_ms(self) -> int:
        return math.gcd(self.size, self.slide)

    @property
    def size_ms(self) -> int:
        return self.size

    @property
    def slide_ms(self) -> int:
        return self.slide

    @property
    def offset_ms(self) -> int:
        return self.offset


@dataclasses.dataclass(frozen=True)
class TumblingProcessingTimeWindows(WindowAssigner):
    """Tumbling windows over PROCESSING time (ref: assigners/
    TumblingProcessingTimeWindows.java). Records are assigned by the
    operator's clock at ingest, and firing is driven by the same clock
    advancing between steps — the pane machinery is identical to the
    event-time assigners, with arrival time as the time axis (so there
    is no lateness and no out-of-orderness by construction)."""

    size: int
    offset: int = 0
    is_event_time = False
    is_processing_time = True

    @classmethod
    def of(cls, size_ms: int, offset_ms: int = 0) -> "TumblingProcessingTimeWindows":
        return cls(size_ms, offset_ms)

    @property
    def pane_ms(self) -> int:
        return self.size

    @property
    def size_ms(self) -> int:
        return self.size

    @property
    def slide_ms(self) -> int:
        return self.size

    @property
    def offset_ms(self) -> int:
        return self.offset


@dataclasses.dataclass(frozen=True)
class SlidingProcessingTimeWindows(WindowAssigner):
    """ref: assigners/SlidingProcessingTimeWindows.java — pane-lowered
    like SlidingEventTimeWindows, over the processing-time axis."""

    size: int
    slide: int
    offset: int = 0
    is_event_time = False
    is_processing_time = True

    @classmethod
    def of(cls, size_ms: int, slide_ms: int,
           offset_ms: int = 0) -> "SlidingProcessingTimeWindows":
        return cls(size_ms, slide_ms, offset_ms)

    def __post_init__(self) -> None:
        if self.size <= 0 or self.slide <= 0:
            raise ValueError("size and slide must be positive")

    @property
    def pane_ms(self) -> int:
        return math.gcd(self.size, self.slide)

    @property
    def size_ms(self) -> int:
        return self.size

    @property
    def slide_ms(self) -> int:
        return self.slide

    @property
    def offset_ms(self) -> int:
        return self.offset


@dataclasses.dataclass(frozen=True)
class EventTimeSessionWindows(WindowAssigner):
    """Gap-merged sessions (ref: assigners/EventTimeSessionWindows.java,
    runtime merge logic in MergingWindowSet.java). Dynamic merging cannot
    be a static pane layout. A job without allowed lateness, retraction
    or a mesh keeps its sessions on the device (ops/session_device.py:
    per key slot a few session lanes, a batch sorted by (slot, ts) and
    merged into them in one program, the host keeping the key directory
    alone); every other session job runs on the host's columnar span
    registry (ops/session.py). The driver chooses by what the job is.
    """

    gap: int
    is_merging = True

    @classmethod
    def with_gap(cls, gap_ms: int) -> "EventTimeSessionWindows":
        return cls(gap_ms)

    @property
    def pane_ms(self) -> int:
        raise TypeError("session windows are not pane-decomposable")


@dataclasses.dataclass(frozen=True)
class GlobalWindows(WindowAssigner):
    """One eternal window; only fires via a (count/custom) trigger
    (ref: assigners/GlobalWindows.java)."""

    is_event_time = False

    @classmethod
    def create(cls) -> "GlobalWindows":
        return cls()

    @property
    def pane_ms(self) -> int:
        raise TypeError("global windows are not pane-decomposable")


# ---------------------------------------------------------------------------
# Triggers. ref: triggers/Trigger.java — onElement/onEventTime/
# onProcessingTime returning CONTINUE/FIRE/PURGE/FIRE_AND_PURGE.
#
# TPU lowering: EventTimeTrigger is evaluated as a vectorized mask over
# (key, pane) cells per watermark advance (no per-key callbacks);
# CountTrigger compares the always-present count lane against the
# threshold at step granularity.
# ---------------------------------------------------------------------------

class TriggerResult:
    CONTINUE = "CONTINUE"
    FIRE = "FIRE"
    PURGE = "PURGE"
    FIRE_AND_PURGE = "FIRE_AND_PURGE"


class Trigger:
    def on_element(self, timestamp: int, window: TimeWindow, count: int) -> str:
        return TriggerResult.CONTINUE

    def on_event_time(self, time: int, window: TimeWindow) -> str:
        return TriggerResult.CONTINUE

    def fires_on_watermark(self) -> bool:
        """Whether the device fire-mask path applies (event-time family)."""
        return False


class EventTimeTrigger(Trigger):
    """FIRE when watermark passes window.max_timestamp
    (ref: triggers/EventTimeTrigger.java)."""

    @classmethod
    def create(cls) -> "EventTimeTrigger":
        return cls()

    def on_event_time(self, time: int, window: TimeWindow) -> str:
        return TriggerResult.FIRE if time >= window.max_timestamp() else TriggerResult.CONTINUE

    def fires_on_watermark(self) -> bool:
        return True


class ProcessingTimeTrigger(Trigger):
    """FIRE when the processing-time clock passes window.max_timestamp
    (ref: triggers/ProcessingTimeTrigger.java). The default trigger of
    the processing-time assigners; evaluated as the same vectorized
    fire mask as EventTimeTrigger, over the clock instead of the
    watermark."""

    @classmethod
    def create(cls) -> "ProcessingTimeTrigger":
        return cls()

    def on_processing_time(self, time: int, window: TimeWindow) -> str:
        return (TriggerResult.FIRE if time >= window.max_timestamp()
                else TriggerResult.CONTINUE)

    def fires_on_watermark(self) -> bool:
        return False


@dataclasses.dataclass(frozen=True)
class CountTrigger(Trigger):
    """FIRE every N elements per (key, window) (ref: triggers/CountTrigger
    .java). Device lowering checks the count lane after each step, so a
    fire can be up to one microbatch late relative to the reference's
    exact-Nth-element semantics — documented batching tradeoff."""

    max_count: int

    @classmethod
    def of(cls, n: int) -> "CountTrigger":
        return cls(n)

    def on_element(self, timestamp: int, window: TimeWindow, count: int) -> str:
        return TriggerResult.FIRE if count >= self.max_count else TriggerResult.CONTINUE


@dataclasses.dataclass(frozen=True)
class PurgingTrigger(Trigger):
    """Wraps a trigger, turning FIRE into FIRE_AND_PURGE
    (ref: triggers/PurgingTrigger.java)."""

    inner: Trigger

    @classmethod
    def of(cls, inner: Trigger) -> "PurgingTrigger":
        return cls(inner)

    def on_element(self, timestamp: int, window: TimeWindow, count: int) -> str:
        r = self.inner.on_element(timestamp, window, count)
        return TriggerResult.FIRE_AND_PURGE if r == TriggerResult.FIRE else r

    def on_event_time(self, time: int, window: TimeWindow) -> str:
        r = self.inner.on_event_time(time, window)
        return TriggerResult.FIRE_AND_PURGE if r == TriggerResult.FIRE else r

    def fires_on_watermark(self) -> bool:
        return self.inner.fires_on_watermark()
