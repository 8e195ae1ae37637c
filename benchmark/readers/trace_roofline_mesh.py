"""Reader: one device's share of its memory roofline in a sharded step,
in percent: ``mesh_step_bytes() / peak HBM bytes per s`` over the device
time per batch of the programs matching ``match`` on ``XLA Modules``
(busiest device). The shapes come from the configuration's module
(``step_shapes``: ``records``, ``devices``); the state's size is the
program's ``memory.hbm_state_bytes``, which under a mesh is one
device's share already. Nothing is read without a device plane, a byte
model or batches in the traced span."""
from benchmark.mesh_step_bytes import mesh_step_bytes
from benchmark.step_bytes import load_peaks
from benchmark.trace_reduce import MODULES_LINE


def read(ctx, match):
    trace = ctx.get("trace")
    dev = trace.busiest() if trace is not None else None
    state = ctx["job_metrics"].get("memory.hbm_state_bytes")
    batches = ctx["trace_batches"]
    shapes = ctx.get("step_shapes")
    if dev is None or not state or not batches or not shapes \
            or "devices" not in shapes:
        return None
    calls, secs = dev.seconds(MODULES_LINE, match)
    if not calls:
        return None
    peaks = load_peaks(ctx["device_kind"])
    need = mesh_step_bytes(state_bytes=int(state), fires=ctx["fires"] > 0,
                           **shapes)
    return 100.0 * (need / peaks["hbm_bytes_per_s"]) / (secs / batches)
