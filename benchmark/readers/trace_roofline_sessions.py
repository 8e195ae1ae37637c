"""Reader: the share of its memory roofline that one of the session
programs reaches, in percent (``session_step_bytes`` has the byte model
and says which program each share is of).

args: ``of`` — ``apply``: ``apply_bytes`` over the device time a BATCH
of the programs matching ``match`` (one call a batch); ``fire``:
``fire_bytes`` over their device time a CALL (a pass);
``match`` — regex over program names on ``XLA Modules``, busiest device.

Nothing is read without a device plane, a matching program (a program
without a device session operator has none) or the module's
``step_shapes`` with ``keys``, ``slots`` and ``lanes``."""
from benchmark.session_step_bytes import apply_bytes, fire_bytes
from benchmark.step_bytes import load_peaks
from benchmark.trace_reduce import MODULES_LINE


def read(ctx, of, match):
    trace = ctx.get("trace")
    dev = trace.busiest() if trace is not None else None
    shapes = ctx.get("step_shapes")
    if dev is None or not shapes or not {
            "records", "keys", "slots", "lanes"} <= set(shapes):
        return None
    calls, secs = dev.seconds(MODULES_LINE, match)
    if not calls or secs <= 0:
        return None
    if of == "apply":
        batches = ctx["trace_batches"]
        if not batches:
            return None
        need, each = apply_bytes(**shapes), secs / batches
    else:
        need, each = fire_bytes(**shapes), secs / calls
    peak = load_peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / each
