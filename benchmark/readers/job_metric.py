"""Reader: a number from ``JobResult.metrics`` of the measured job.

args: ``sum`` — fnmatch patterns over metric names, summed;
``per`` — ``batches`` | ``fires`` | ``window_s`` | absent;
``scale`` — multiplied in last (1000 for s -> ms, 100 for a share)."""
import fnmatch


def read(ctx, sum, per=None, scale=1.0):
    metrics = ctx["job_metrics"]
    names = [k for k in metrics for pat in sum if fnmatch.fnmatch(k, pat)]
    if not names:
        return None
    total = 0.0
    for k in set(names):
        total += float(metrics[k])
    if per is not None:
        den = ctx[per]
        if not den:
            return None
        total /= den
    return total * scale
