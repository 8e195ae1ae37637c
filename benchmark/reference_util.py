"""Helpers for the plain references: they are numpy over hundreds of
millions of events after every run, so the per-batch work goes to a few
threads (numpy releases the interpreter lock) and arrives back in order."""
from __future__ import annotations

import collections
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Iterator, Tuple

import numpy as np

WORKERS = 4


def blocks_in_order(stream: Iterable[Tuple[dict, np.ndarray]],
                    fn: Callable[[dict, np.ndarray], tuple]
                    ) -> Iterator[tuple]:
    """``fn(data, ts)`` of every batch of ``stream``, in stream order."""
    with ThreadPoolExecutor(WORKERS) as pool:
        pending: collections.deque = collections.deque()
        for data, ts in stream:
            pending.append(pool.submit(fn, data, ts))
            if len(pending) >= 2 * WORKERS:
                yield pending.popleft().result()
        while pending:
            yield pending.popleft().result()


def counts_by_bucket(stream, bucket_ms: int, key_field: str, n_keys: int,
                     n_buckets: int) -> np.ndarray:
    """(n_buckets, n_keys) int32 occurrence counts; bucket = ts //
    bucket_ms. int32: a run offers a few 10^9 events, a bucket holds at
    most a batch or two of them."""
    def one(data, ts):
        b = np.asarray(ts, np.int64) // bucket_ms
        b0, b1 = int(b.min()), int(b.max())
        b -= b0             # in place: fresh 8 MB temporaries cost more
        b *= n_keys         # than the arithmetic
        b += data[key_field]
        return b0, b1, np.bincount(
            b, minlength=(b1 - b0 + 1) * n_keys).astype(np.int32)

    counts = np.zeros((n_buckets, n_keys), np.int32)
    for b0, b1, block in blocks_in_order(stream, one):
        counts[b0:b1 + 1] += block.reshape(-1, n_keys)
    return counts
