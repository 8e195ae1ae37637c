"""Traffic kind ``constant_rate_delayed``: events HAPPEN evenly in event
time, as in ``constant_rate``, and a drawn share of them is handed over
late, with the timestamp it happened at: the stream is not in order.

Parameters of a mix of this kind (``benchmark/traffic/<mix>.json``):

- ``events_per_ms``: event ``k`` of the generator's stream is stamped
  ``k // events_per_ms`` (``constant_rate``'s stamp);
- ``prob_delayed``, ``occasional_delay_ms``: the share of events that
  is held back, and the length under which each one's delay is drawn,
  uniformly (the NEXmark generator configuration's ``probDelayedEvent``
  and ``occasionalDelaySec``); the rest are offered when they happen;
- ``delay_seed``: what draws them (a ``Schedule`` is given no ``--seed``);
- ``paced``: as in ``constant_rate``.

THE CONSTRUCTION (``Arrivals``): sort by due time, cut by count. Event
``k`` falls DUE at index ``u = k + delay(k)`` of the stream, its delay
counted in events (``occasional_delay_ms x events_per_ms`` of them at
most); the offered stream is every event ``k >= 0`` in the order of
``(u, k)``, and batch ``i`` is places ``[i * n, (i + 1) * n)`` of it. So
every event is offered exactly once, every batch has ``n`` rows, and
nothing is offered before it happened. What is drawn is drawn per PLACE
OF THE GENERATOR'S BATCH: ``delay(k)`` depends on ``k mod n`` alone
(``n`` draws, not one a run's event), which makes the offered stream
periodic: once it has run ``occasional_delay_ms`` (the ramp, in which
fewer events fall due than happen, so a batch takes ~1/0.9 as long to
fill), batch ``i + 1`` is batch ``i`` with every index ``n`` higher.
Batch ``i`` is then ``i * n`` plus one vector of offsets, and its
timestamps cost three vector passes and no division.

Inside a batch (the job is handed it whole, so its order says nothing
about time): first the held-back rows, in due order (what a source that
caught up with a slow partition hands over), then the on-time rows in
the order they happened. The last row is the batch's newest event (a
held-back row whose delay was a few events changes places with it, if
it is newer), and ``BenchSource`` releases the batch when that one is
due: no row before its own timestamp, and a held-back row up to one
batch's fill after its delay ran out, as every on-time row waits for
its batch to fill.

A kind is a class ``Schedule(params)`` with ``batch_ts(index, n)`` and
``events_per_ms``; a configuration whose record content has to follow
the permutation (``configs/nexmark_q5_delayed.py``) takes ``Arrivals``
from here, with the same parameters (a test holds them equal).
"""
from __future__ import annotations

import numpy as np


class Arrivals:
    """Which event of the generator's stream each row of each batch of
    ``n`` rows carries: ``indices(i)``."""

    def __init__(self, n: int, events_per_ms: int, prob_delayed: float,
                 occasional_delay_ms: int, delay_seed: int) -> None:
        self.n = n = int(n)
        rng = np.random.default_rng(int(delay_seed))
        place = np.arange(n, dtype=np.int64)
        held = rng.random(n) < float(prob_delayed)
        delay = (rng.random(n) * (int(occasional_delay_ms)
                                  * int(events_per_ms))).astype(np.int64)
        # event q * n + place falls due at index (q + lag) * n + at
        due = place + np.where(held, delay, 0)
        lag, at = due // n, due % n
        # one period of the due order: n events, one of each place
        order = np.lexsort((place, at))
        self._place, self._lag = place[order], lag[order]
        self._on_time = ~held[order]
        # period a holds the places whose lag it has reached: the ramp's
        # periods are short of the places still held back
        self._ramp = int(lag.max())
        sizes = np.cumsum(np.bincount(lag, minlength=self._ramp + 1))
        self._start = np.concatenate([[0], np.cumsum(sizes)])
        # from this batch on, batch i is i * n + ``offsets``; a ramp
        # batch's offsets are kept once made (4 bytes a row: the warm-up
        # has asked for every one before the window opens)
        self.steady_from = -(-int(self._start[self._ramp]) // n)
        self.offsets = self._cut(self.steady_from) - self.steady_from * n
        self._ramp_offsets: dict = {}

    def _period(self, a: int):
        """The events due in ``[a * n, (a + 1) * n)``, in due order, and
        which of them are on time."""
        k = (a - self._lag) * self.n + self._place
        if a >= self._ramp:
            return k, self._on_time
        there = self._lag <= a
        return k[there], self._on_time[there]

    def indices(self, i: int) -> np.ndarray:
        """The generator indices of batch ``i``'s rows."""
        if i >= self.steady_from:
            return i * self.n + self.offsets
        if i not in self._ramp_offsets:
            self._ramp_offsets[i] = (self._cut(i) - i * self.n
                                     ).astype(np.int32)
        return i * self.n + self._ramp_offsets[i].astype(np.int64)

    def _cut(self, i: int) -> np.ndarray:
        n, ramp = self.n, self._ramp
        lo = i * n
        if lo >= self._start[ramp]:
            a = ramp + (lo - int(self._start[ramp])) // n
            skip = (lo - int(self._start[ramp])) % n
        else:
            a = int(np.searchsorted(self._start, lo, side="right")) - 1
            skip = lo - int(self._start[a])
        ks, on = [], []
        need = n
        while need > 0:
            k, o = self._period(a)
            ks.append(k[skip:skip + need])
            on.append(o[skip:skip + need])
            need -= len(ks[-1])
            a, skip = a + 1, 0
        k, on = np.concatenate(ks), np.concatenate(on)
        k = np.concatenate([k[~on], k[on]])
        newest = int(k.argmax())
        k[newest], k[-1] = k[-1], k[newest]
        return k


class Schedule:
    def __init__(self, params: dict) -> None:
        rate = params["events_per_ms"]
        if int(rate) != rate or rate <= 0:
            raise ValueError(f"events_per_ms must be a positive whole "
                             f"number: {rate!r}")
        self.events_per_ms = int(rate)
        self._delays = tuple(params[k] for k in (
            "prob_delayed", "occasional_delay_ms", "delay_seed"))
        self._arrivals = None
        self._table = None

    def arrivals(self, n: int) -> Arrivals:
        if self._arrivals is None or self._arrivals.n != n:
            self._arrivals = Arrivals(n, self.events_per_ms, *self._delays)
            # (start + off) // r = start // r + off // r
            #                      + (start % r + off % r >= r)
            off = self._arrivals.offsets
            self._table = (off // self.events_per_ms,
                           (off % self.events_per_ms).astype(np.int32))
        return self._arrivals

    def batch_ts(self, index: int, n: int) -> np.ndarray:
        arr, r = self.arrivals(n), self.events_per_ms
        if index < arr.steady_from:
            return arr.indices(index) // r      # the ramp: ~30 batches
        quot, rem = self._table
        start = index * n
        return quot + (start // r + (rem >= r - start % r))
