"""Reader: the load generator's own clock (``loadgen.BenchSource``).

args: ``series`` — ``late_s`` (release - due, paced) | ``gen_s``
(generation per batch); ``stat`` — ``mean`` | ``p<q>``; ``scale``."""
from benchmark.stats import percentile


def read(ctx, series, stat, scale=1.0):
    xs = ctx["generator"].get(series) or []
    if not xs:
        return None
    v = sum(xs) / len(xs) if stat == "mean" else percentile(xs, float(stat[1:]))
    return v * scale
