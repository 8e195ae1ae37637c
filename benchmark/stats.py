"""Arithmetic the benchmark's numbers rest on, kept apart so tests can
hold it to hand-worked cases."""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default)."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no samples")
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the contract measures it (statistics.quantiles, n=4)."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def fire_latencies_ms(first_arrival_s: Dict[int, float], t_open: float,
                      out_of_orderness_ms: int, max_ts_ms: int
                      ) -> List[float]:
    """Event-time latency of each window a paced run fired on its own.

    A window ending at ``W`` can fire once an event stamped
    ``>= W + out_of_orderness`` has been seen (the watermark then
    passes ``W - 1``). In a paced run that event is DUE at ``t_open +
    (W + out_of_orderness)`` ms, whatever the job's state; the sample is
    the arrival of the window's first row at the sink minus that due
    time. It counts the wait for the batch to fill, any queue or
    backlog, the device step and the drain; it leaves out the window's
    length and the configured watermark delay. Windows that only the
    end-of-input flush fired (no such event was ever offered) are not
    samples.
    """
    out = []
    for end, arrived in sorted(first_arrival_s.items()):
        trigger_ts = end + out_of_orderness_ms
        if trigger_ts > max_ts_ms:
            continue
        out.append((arrived - (t_open + trigger_ts / 1e3)) * 1e3)
    return out


def lag_slope_ms_per_s(release_s: Sequence[float], late_s: Sequence[float]
                       ) -> Optional[float]:
    """Least-squares slope of the source's lateness over the window: ms
    of lag gained per second. Near zero = the rate is sustained."""
    n = len(late_s)
    if n < 3:
        return None
    xs = [t - release_s[0] for t in release_s]
    mx, my = sum(xs) / n, sum(late_s) / n
    den = sum((x - mx) ** 2 for x in xs)
    if den == 0:
        return None
    return 1e3 * sum((x - mx) * (y - my) for x, y in zip(xs, late_s)) / den
