"""Reader: the share of its memory roofline that ONE of the large-state
programs reaches, in percent (``large_keys_step_bytes`` has the byte
model and says which program each share is of).

args: ``of`` — ``apply``: ``apply_bytes(records)`` over the device time
a BATCH of the programs matching ``match`` (one call a batch);
``fire``: ``fire_bytes(state_bytes)`` over their device time a CALL
(a fire runs once a window end, not once a batch);
``match`` — regex over program names on ``XLA Modules``, busiest device.

Nothing is read without a device plane, a matching program, the
module's ``step_shapes`` (``records``) or the program's
``memory.hbm_state_bytes``."""
from benchmark.large_keys_step_bytes import apply_bytes, fire_bytes
from benchmark.step_bytes import load_peaks
from benchmark.trace_reduce import MODULES_LINE


def read(ctx, of, match):
    trace = ctx.get("trace")
    dev = trace.busiest() if trace is not None else None
    if dev is None:
        return None
    calls, secs = dev.seconds(MODULES_LINE, match)
    if not calls or secs <= 0:
        return None
    if of == "apply":
        shapes = ctx.get("step_shapes")
        batches = ctx["trace_batches"]
        if not shapes or "records" not in shapes or not batches:
            return None
        need, each = apply_bytes(records=shapes["records"]), secs / batches
    else:
        state = ctx["job_metrics"].get("memory.hbm_state_bytes")
        if not state:
            return None
        need = fire_bytes(state_bytes=int(state) // max(1, ctx["chips"]))
        each = secs / calls
    peak = load_peaks(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / peak) / each
