"""The float-sum probe for ``nexmark_q5_large_keys``: ``float_sum``'s job
and float64 reference (that module, imported and not copied) on this
configuration's first batch, at a slot budget and under a limit of its
own.

Why its own budget. ``float_sum.run`` builds its job under the
configuration's ``conf_overrides``: 128 x 131,072 slots. The probe's job
has no top-n, so it fires through ``fire_pack_kernel``, which pads every
fire to 64 window ends: 16.8 M rows x 64 does not fit the chip. The
probe's one batch names 68,385 auctions; ``conf_overrides`` of the
``probe`` entry (128 x 1,024 slots) hold them, and the batch takes the
SAME ingest lane as the cell: 68 k keys over the batch's 6 panes are too
many (slot, pane) pairs for the pre-aggregated upload, so every record
crosses the link and ``apply_kernel`` scatters it.

Why its own limit. On that lane every price is added to its pane in
float32 on the device, in arrival order (~770 prices a hot auction and
pane), where the recurring-keys job adds a batch's prices on the host in
float64 first (``float_sum`` reads 1.4e-7). ``float_sum_mesh
.mesh_lane_sums`` is this lane in numpy: one float32 add a record, in
order. What this probe guards is what ``float_sum`` guards, on the
programs this cell runs: the float payload of the per-record upload, the
scatter-add of ``apply_kernel``, and the fire's dot, which must stay at
``Precision.HIGHEST`` (PR 21's fault: 3.9e-3 off).

``SUM_RTOL`` lies between two readings (PERF.md section 2 gives them):
the largest a sound run reads over seeds, and the smallest the control
reads: the same job with the fire's dot lowered to ``Precision.HIGH``
(``tools/probe_control_large_keys.py``, on the chip).
"""
from __future__ import annotations

from benchmark.probes import float_sum, float_sum_mesh

# limit on max |sum - f64 reference| / reference over all committed rows
SUM_RTOL = 5e-6

SCHEMA = float_sum.SCHEMA
build = float_sum.build
records = float_sum.records
mesh_lane_sums = float_sum_mesh.mesh_lane_sums   # the lane, in numpy


class _AtProbeSize:
    """The run's configuration with the probe's own slot budget."""

    def __init__(self, config, spec: dict, rehearsal: bool) -> None:
        self.params, self.module = config.params, config.module
        self.conf_overrides = dict(config.conf_overrides)
        if not rehearsal:   # a rehearsal's cut is small enough already
            self.conf_overrides.update(spec.get("conf_overrides", {}))


def judge(out: dict) -> dict:
    """``float_sum``'s verdict on a probe's rows, at this module's limit."""
    out["sum_rtol"] = SUM_RTOL
    out["holds"] = bool(
        out["rows_got"] == out["rows_expected"] == out["rows_unique"]
        and out["counts_differing"] == 0
        and out["sum_max_rel_err"] <= SUM_RTOL)
    return out


def run(config, spec: dict, seed: int, rehearsal: bool, harness) -> dict:
    out = judge(float_sum.run(_AtProbeSize(config, spec, rehearsal), spec,
                              seed, rehearsal, harness))
    out["compared"]["sum_max_rel_err"][1] = SUM_RTOL
    return out
