"""The float-sum probe for a configuration that runs on a device mesh:
``float_sum``'s job, records and float64 reference (that module,
imported and not copied), under a limit of its own.

Why another limit. On one device the job adds a batch's prices per
(slot, pane) on the host in float64 before the upload, so a committed
sum is off the float64 reference by one float32 rounding per batch and
pane (1.1e-7 to 1.5e-7, ``float_sum``'s readings). Under a mesh every
record crosses the keyed exchange as one entry and the device that owns
its key adds it to the pane in float32, in arrival order: about 830
prices a hot key and pane in the probe's batch, which reads 2e-6. That
is the lane as ISSUE 26 has it (no pre-aggregation before the exchange),
not a fault, so ``float_sum``'s 1e-6 cannot judge it. What this probe
guards is what ``float_sum`` guards on one device, on the kernels only
the mesh runs: the float payload through ``keyby_exchange``, the scatter
after the ``all_to_all``, and the window sum in the sharded fire, whose
dot must stay at ``Precision.HIGHEST`` (PR 21's fault: 3.9e-3 off).

``SUM_RTOL`` lies between two readings (PERF.md section 2 gives them):
the largest a sound run reads over seeds (``mesh_lane_sums`` below is
that lane in numpy, bit for bit what four devices give), and the
smallest the control reads: the same float32 pane sums through the
fire's dot at ``Precision.HIGH`` (``float_sum.lower_precision_sums``;
on the chip ``tools/probe_control_mesh.py`` runs the job itself with the
dot lowered).
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from benchmark.probes import float_sum

# limit on max |sum - f64 reference| / reference over all committed rows
SUM_RTOL = 5e-6

SCHEMA = float_sum.SCHEMA
build = float_sum.build
records = float_sum.records


def mesh_lane_sums(data: Dict[str, np.ndarray], ts: np.ndarray,
                   p: dict) -> np.ndarray:
    """(n_panes, auction ids) price sums as the mesh lane makes them:
    every price added to its pane in float32, in the order of arrival
    (the exchange keeps a key's records in order)."""
    a = int(data["auction"].max()) + 1
    pane = np.asarray(ts, np.int64) // int(p["slide_ms"])
    n_panes = int(pane.max()) + 1
    acc = np.zeros(n_panes * a, np.float32)
    np.add.at(acc, pane * a + data["auction"],
              data["price"].astype(np.float32))
    return acc.reshape(n_panes, a)


def judge(out: dict) -> dict:
    """``float_sum``'s verdict on a probe's rows, at this module's limit."""
    out["sum_rtol"] = SUM_RTOL
    out["holds"] = bool(
        out["rows_got"] == out["rows_expected"] == out["rows_unique"]
        and out["counts_differing"] == 0
        and out["sum_max_rel_err"] <= SUM_RTOL)
    return out


def check_rows(sink_batches, data, ts, p: dict) -> dict:
    return judge(float_sum.check_rows(sink_batches, data, ts, p))


def run(config, spec: dict, seed: int, rehearsal: bool, harness) -> dict:
    """``float_sum.run`` (the job takes the configuration's
    ``conf_overrides``, so it runs on the mesh), compared at ``SUM_RTOL``."""
    out = judge(float_sum.run(config, spec, seed, rehearsal, harness))
    out["compared"]["sum_max_rel_err"][1] = SUM_RTOL
    return out
