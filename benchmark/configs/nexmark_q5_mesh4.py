"""NEXmark query 5 over a device mesh: the job, records and plain
reference of ``nexmark_q5`` (imported, not copied: the generator and the
reference know nothing of partitions, so they are the same code at any
parallelism), plus what only a key-sharded deployment has:

- ``zero_counters`` adds ``exchange_devices_idle``: the program's count
  of mesh devices that held no pane-state rows or received no record
  over the job. It is 0 only when every device of the mesh did the work.
  (A program that has no such gauge yet reads 0, as every absent counter
  does; that the job runs on the mesh at all is ``build``'s to see to.)
- ``build`` refuses a conf whose ``cluster.mesh-devices`` is not the
  configuration's ``mesh_devices`` (= its ``parallelism``, the source's
  name for it): the deployment is the mesh.
- ``step_shapes`` gives ``mesh_step_bytes.mesh_step_bytes`` the shapes of
  one device's share of a step.
"""
from __future__ import annotations

from typing import Tuple

from benchmark.configs import nexmark_q5 as q5

SCHEMA = q5.SCHEMA
WINDOW_END_FIELD = q5.WINDOW_END_FIELD
make_pool = q5.make_pool
check = q5.check
fire_delay_ms = q5.fire_delay_ms
warmup_event_ms = q5.warmup_event_ms


def zero_counters(p: dict) -> Tuple[str, ...]:
    return q5.zero_counters(p) + ("exchange_devices_idle",)


def step_shapes(p: dict, batch: int, events_per_ms: float) -> dict:
    """What ``mesh_step_bytes.mesh_step_bytes`` needs to know of one
    device's share of one sharded step."""
    return {"records": batch, "devices": int(p["mesh_devices"])}


def build(env, source, sink, p: dict) -> None:
    from flink_tpu.config import ClusterOptions

    asked = str(env.config.get(ClusterOptions.MESH_DEVICES)).strip()
    if not asked == str(int(p["mesh_devices"])) == str(int(p["parallelism"])):
        raise ValueError(
            f"this configuration is Q5 at parallelism {p['parallelism']} "
            f"on a mesh of {p['mesh_devices']} devices; the job's conf "
            f"has cluster.mesh-devices = {asked!r}")
    q5.build(env, source, sink, p)
