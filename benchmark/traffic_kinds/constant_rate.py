"""Traffic kind ``constant_rate``: events arrive evenly in event time.

Parameters of a mix of this kind (``benchmark/traffic/<mix>.json``):

- ``events_per_ms``: a whole number of events per millisecond of event
  time; event ``i`` of the stream is stamped ``i // events_per_ms``, so
  timestamps never go backwards (the NEXmark generator's default: its
  ``outOfOrderGroupSize`` is 1).
- ``paced`` (read by ``loadgen.BenchSource``, not here): ``false`` — the
  backlog is always there; ``true`` — an event stamped ``t`` ms is DUE at
  window open + ``t`` ms of wall time.

A kind is a class ``Schedule(params)`` with ``batch_ts(index, n)`` (the
int64 timestamps of events ``[index * n, (index + 1) * n)``) and
``events_per_ms`` (the mean density, for sizing the warm-up).
"""
from __future__ import annotations

import numpy as np


class Schedule:
    def __init__(self, params: dict) -> None:
        rate = params["events_per_ms"]
        if int(rate) != rate or rate <= 0:
            raise ValueError(f"events_per_ms must be a positive whole "
                             f"number: {rate!r}")
        self.events_per_ms = int(rate)
        self._table = None

    def batch_ts(self, index: int, n: int) -> np.ndarray:
        # (start + i) // r = start // r + (start % r + i) // r, and the
        # second term's table serves every batch (64-bit division is
        # what a batch's timestamps cost otherwise)
        r = self.events_per_ms
        if self._table is None or len(self._table[0]) != n:
            i = np.arange(n, dtype=np.int64)
            self._table = (i // r, (i % r).astype(np.int32))
        quot, rem = self._table
        start = index * n
        return quot + (start // r + (rem >= r - start % r))
