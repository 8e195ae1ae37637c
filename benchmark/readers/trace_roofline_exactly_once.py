"""Reader: the share of its memory roofline that the checkpoint's device
clone reaches, in percent: ``exactly_once_step_bytes.clone_bytes`` over
peak HBM bytes per s, over the device time a CALL of the programs
matching ``match`` on ``XLA Modules`` (busiest device; a clone runs once
a checkpoint, not once a batch).

The tensor's shape is the program's own: the job metrics
``state.pane_rows`` and ``state.ring_columns``. Nothing is read without
a device plane, a matching program in the traced span (no checkpoint
fell into it, or the program gives its clone no name) or those two
metrics."""
from benchmark.exactly_once_step_bytes import clone_bytes
from benchmark.step_bytes import load_peaks
from benchmark.trace_reduce import MODULES_LINE


def read(ctx, match):
    trace = ctx.get("trace")
    dev = trace.busiest() if trace is not None else None
    rows = ctx["job_metrics"].get("state.pane_rows")
    ring = ctx["job_metrics"].get("state.ring_columns")
    if dev is None or not rows or not ring:
        return None
    calls, secs = dev.seconds(MODULES_LINE, match)
    if not calls or secs <= 0:
        return None
    need = clone_bytes(rows=int(rows), ring=int(ring))
    peak = load_peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / (secs / calls)
