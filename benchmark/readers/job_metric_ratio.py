"""Reader: a sum of ``JobResult.metrics`` of the measured job over
another sum of them (``job_metric``'s ``per`` knows only batches, fires
and seconds): seconds of a checkpoint's part over the checkpoints
completed, bytes over checkpoints.

args: ``sum`` and ``over`` — fnmatch patterns over metric names, each
set summed; ``scale`` — multiplied in last (1000 for s -> ms). Nothing
to read (no metric matches one of the two sets, as on a program that
does not count them, or the lower sum is 0): ``None``."""
import fnmatch


def total(metrics, patterns):
    names = {k for k in metrics for pat in patterns
             if fnmatch.fnmatch(k, pat)}
    return sum(float(metrics[k]) for k in names) if names else None


def read(ctx, sum, over, scale=1.0):
    metrics = ctx["job_metrics"]
    num, den = total(metrics, sum), total(metrics, over)
    if num is None or not den:
        return None
    return num / den * scale
