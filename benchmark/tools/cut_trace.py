"""Run one traced cell and keep a small cut of its profiler trace: how the
recorded ``tests/*.xplane.pb`` files are made.

    python benchmark/tools/cut_trace.py --out chiprun_out/cut.xplane.pb \
        -- --workload q5_hostfed_replay --seed 7 --seconds 45 --trace 1

Everything after ``--`` goes to ``benchmark/run.py``, in this process (a
chip belongs to one process). ``run.py`` throws its trace directory away;
this keeps the newest ``.xplane.pb`` of it first, cut to ``--seconds`` of
the traced span from ``--skip`` seconds in: each device plane's ``XLA
Modules`` and ``XLA Ops`` lines whole, and of the host plane every event of
``--min-host-us`` and more plus every one whose name matches ``--keep``
(the program's own spans), one line per thread, re-encoded with
``tests/xspace.py``. Tens of KB instead of tens of MB.
"""
from __future__ import annotations

import argparse
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[0] = ROOT

PROGRAM_SPANS = r"^(ingest|window|wm|drain|checkpoint)\."


def cut(path: str, skip_s: float, seconds: float, min_host_ns: float,
        keep: str) -> bytes:
    from jax.profiler import ProfileData

    from benchmark import trace_reduce as tr

    sys.path.insert(0, os.path.join(ROOT, "benchmark", "tests"))
    from xspace import xspace

    planes = list(ProfileData.from_file(path).planes)
    starts = [e.start_ns for p in planes for ln in p.lines for e in ln.events
              if tr.DEVICE_PLANE.match(p.name)
              or p.name.startswith("/host:CPU")]
    lo = min(starts) + skip_s * 1e9
    hi = lo + seconds * 1e9
    rx = re.compile(keep)
    out = []
    for p in planes:
        device = bool(tr.DEVICE_PLANE.match(p.name))
        if not device and not p.name.startswith("/host:CPU"):
            continue
        lines = []
        for i, ln in enumerate(p.lines):
            if device and ln.name not in (tr.MODULES_LINE, tr.OPS_LINE):
                continue
            evs = [(e.name, int(e.start_ns - lo), int(e.duration_ns))
                   for e in ln.events
                   if lo <= e.start_ns and e.start_ns + e.duration_ns <= hi
                   and (device or rx.search(e.name)
                        or (e.duration_ns >= min_host_ns
                            and not tr.HOST_NOISE.match(e.name)))]
            if evs:
                # threads may share a name: one line each all the same
                lines.append((ln.name if device else f"{ln.name}/{i}", evs))
        out.append((p.name, lines))
    return xspace(out)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--skip", type=float, default=0.5)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--min-host-us", type=float, default=200.0)
    ap.add_argument("--keep", default=PROGRAM_SPANS)
    ap.add_argument("run_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()

    from benchmark import run, trace_reduce
    from benchmark.readers.trace_host import host_recorded

    close = run.TraceWindow.close

    def keep_then_close(self) -> None:
        path = trace_reduce.newest_xplane(self.dir)
        if path is not None:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "wb") as f:
                f.write(cut(path, args.skip, args.seconds,
                            1e3 * args.min_host_us, args.keep))
            run.log(f"kept {os.path.getsize(args.out)} bytes of "
                    f"{os.path.getsize(path)} in {args.out}")
            whole = trace_reduce.reduce_file(path)
            lo, hi = host_recorded(whole)
            run.log(f"the host tracer recorded {(hi - lo) / 1e9:.3f} s of "
                    f"the {whole.window_s:.3f} s traced, from "
                    f"{(lo - whole.window[0]) / 1e9:.3f} s in")
        close(self)

    run.TraceWindow.close = keep_then_close
    return run.main([a for a in args.run_args if a != "--"])


if __name__ == "__main__":
    sys.exit(main())
