"""Reader: what the benchmark's own clocks saw of the whole window.

args: ``of`` —
``setup``: seconds from process start to window open;
``throughput``: events whose results reached the sink per second, from
window open to the last row of the end-of-input flush;
``latency``: the paced run's per-window event-time latencies, ms (needs
``stat``: ``p<q>`` | ``max``; at least two samples);
``stall``: process-wide stalls the heartbeat saw, as ms of stall per
second of window (traced runs only)."""
from benchmark.stats import percentile


def read(ctx, of, stat=None):
    if of == "setup":
        return ctx["setup_s"]
    if of == "throughput":
        return (ctx["events_offered"] - ctx["events_failed"]) / ctx["window_s"]
    if of == "stall":
        if ctx["stall_s"] is None:
            return None
        return 1e3 * ctx["stall_s"] / ctx["window_s"]
    xs = ctx["latencies_ms"]
    if len(xs) < 2:
        return None
    return max(xs) if stat == "max" else percentile(xs, float(stat[1:]))
