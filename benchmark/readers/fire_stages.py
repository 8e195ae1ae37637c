"""Reader: the median, over the job's per-window fire records, of the time
between two of a record's stamps, in ms.

``JobResult.metrics["trace.fires"]`` holds one record per window end the
job fired (``Driver.fire_records``), stamped on one monotonic clock:
``t_input`` (the source handed over the batch whose timestamps carried
the watermark past the end), ``t_fire`` (fire dispatched), ``t_fetch0`` /
``t_fetch1`` (the drain's fetch of its rows began / ended), ``t_sink``
(``sink.write`` returned). A program that keeps no such records gives
nothing to read.

args: ``start``, ``end`` — the two stamps; records that lack either are
left out."""
from benchmark.stats import percentile


def read(ctx, start, end):
    records = ctx["job_metrics"].get("trace.fires")
    if not records:
        return None
    ms = [1e3 * (r[end] - r[start]) for r in records
          if r.get(start) is not None and r.get(end) is not None]
    if not ms:
        return None
    return percentile(ms, 50)
