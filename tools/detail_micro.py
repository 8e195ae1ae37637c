"""What one ``PhaseClock.detail`` costs with no profiler session: host
only, one JSON line. ``python tools/detail_micro.py [--n 200000]``
(through the chip tool for the chip's host).

ns a block, each the best of five passes, the empty ``with`` taken off:
``leaf_open`` (a detail under an open leaf: two clock reads, one inert
``TraceAnnotation``, one dict update), ``no_leaf`` (a counter only), and
beside them ``span`` (a phase switch and back: what a leaf costs)."""
import argparse
import contextlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from flink_tpu.obs.tracing import PhaseClock  # noqa: E402


def best_ns(make, n: int) -> float:
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with make():
                pass
        best = min(best, time.perf_counter() - t0)
    return 1e9 * best / n


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    n = ap.parse_args().n
    clock = PhaseClock()
    empty = best_ns(contextlib.nullcontext, n)
    no_leaf = best_ns(lambda: clock.detail("wait"), n)
    clock.phase("leaf.a")
    leaf_open = best_ns(lambda: clock.detail("x"), n)
    span = best_ns(lambda: clock.span("leaf.b"), n)
    clock.stop()
    print(json.dumps({
        "n": n, "empty_with_ns": round(empty, 1),
        "detail_leaf_open_ns": round(leaf_open - empty, 1),
        "detail_no_leaf_ns": round(no_leaf - empty, 1),
        "span_ns": round(span - empty, 1)}))


if __name__ == "__main__":
    main()
