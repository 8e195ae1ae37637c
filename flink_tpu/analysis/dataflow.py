"""Dataflow plane of the plan analyzer — abstract interpretation over
the lowered ExecutionPlan.

PR 4's rule engine is linear: every rule sees one node at a time, so a
keyBy on a field the upstream map dropped, a GlobalWindow whose state
grows without bound, or a join leg whose watermark can never advance
all still fail at runtime, after records flow. This module adds the
second plane: ONE topological walk (`propagate`) that interprets the
plan edge-by-edge over three lattices, with the registered dataflow
rules (FIELD_NOT_IN_SCHEMA, SCHEMA_MISMATCH_UNION,
UNBOUNDED_STATE_GROWTH, STALLED_WATERMARK_LEG, NON_TXN_SINK_IN_CHAIN,
STATE_BYTES_EXCEEDED, CHANGELOG_SINK_MISMATCH) reading the propagated
facts — the
graph-compilation-time validation role of the reference's
Transformation → StreamGraph translation (PAPER §2 layer L6), extended
with the state/time facts the multi-tenant admission path needs.

The three lattices:

- **Record schema** — field name → numpy dtype name; ``None`` is the
  lattice top (unknown). Seeded from source declarations
  (``Source.declared_schema``), stepped per op: stateful operators use
  the compiler-recorded ``ExecNode.out_schema`` (the fired-row shape is
  a plan fact); chains are ABSTRACTLY EVALUATED by running their fused
  fns on an EMPTY typed batch (0 rows of the inferred dtypes — the
  dask-style meta-inference trick: dtype/field propagation is exact,
  no data ever flows, and a KeyError IS the field-reference error the
  rule reports). Any other failure degrades the schema to unknown —
  never a finding.
- **State-growth bound** — stateless | bounded | unbounded | opaque,
  with a human-readable shape (keys × live panes, live session spans,
  partial matches) and, for the dense lane layouts, a BYTES-PER-KEY
  estimate derived from the window/lateness geometry — the number
  ``analyze --explain`` prints and ``analysis.max-state-bytes-per-key``
  budgets against. Derived from assigner type, trigger/evictor
  discipline, session gap, and CEP skip strategy.
- **Watermark capability** — which time axis a node's output rows
  carry: ``event`` (event-time watermark meaningful and advancing),
  ``processing`` (proc-time assigners — rows stamped off the operator
  clock), or ``none`` (count/global windows — no time axis at all).
  The pipeline watermark is computed from SOURCE event timestamps
  (time/watermarks.py), so an event-time operator fed by a
  ``processing``/``none`` leg assigns panes the source watermark can
  never meaningfully cross — the stalled-leg shape.

Chain evaluation and side effects: user fns are only ever CALLED on the
explicit analysis surfaces (``env.analyze()`` / `flink_tpu analyze`);
the driver's automatic submit pass runs with chain evaluation OFF
(core.analyze ``eval_chains=False``), so a side-effecting map never
observes a phantom batch just because the job was submitted.

Honest scope: no cross-function taint (a field smuggled through opaque
state is invisible), no symbolic shapes (bytes estimates use the
declared config geometry, not data), and schema facts stop at the
first chain that raises on an empty batch.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import warnings
from typing import Any, Dict, Iterable, List, Optional, Tuple

import numpy as np

from flink_tpu.analysis.core import Finding, plan_rule
from flink_tpu.ops.aggregates import EVENT_TIME_FIELD

Schema = Optional[Dict[str, str]]  # field -> numpy dtype name; None = top

# rules this walk can emit findings for during propagation
_WALK_RULES = ("FIELD_NOT_IN_SCHEMA", "SCHEMA_MISMATCH_UNION")

# chain-evaluation mode, set by core.analyze around the rule loop.
# THREAD-LOCAL: a driver submit pass (eval off) and an explicit
# env.analyze() (eval on) may run on different threads concurrently —
# a module global would let one flip the other's mode mid-loop and
# break the never-call-user-fns-at-submit guarantee.
_STATE = threading.local()


def _eval_chains_enabled() -> bool:
    return getattr(_STATE, "eval_chains", True)


@contextlib.contextmanager
def chain_eval_mode(enabled: bool):
    prev = _eval_chains_enabled()
    _STATE.eval_chains = bool(enabled)
    try:
        yield
    finally:
        _STATE.eval_chains = prev


def _f(message: str, fix: str = "", node=None, node_name: str = "") -> Finding:
    # analyze() stamps the registered rule id + severity
    return Finding(rule="", severity="warn", message=message, fix=fix,
                   node=node, node_name=node_name)


@dataclasses.dataclass
class NodeFacts:
    """The propagated facts of one ExecNode — what `analyze --explain`
    prints and the dataflow rules read."""

    node_id: int
    kind: str
    name: str
    in_schema: Schema = None
    schema: Schema = None          # output schema
    schema_note: str = ""
    state: str = "stateless"       # stateless|bounded|unbounded|opaque
    state_detail: str = ""
    state_bytes_per_key: Optional[int] = None
    wm: str = "event"              # event|processing|none
    wm_note: str = ""
    log_tainted: bool = False      # downstream of a LogSource
    bounded_input: bool = True     # every upstream source is bounded
    # changelog axis: output rows are op-typed (records.OP_FIELD) — set
    # at retract-mode operators, carried through pass-through nodes,
    # reset at re-aggregating operators (their fired rows are fresh)
    changelog: bool = False


@dataclasses.dataclass
class PlanFacts:
    nodes: Dict[int, NodeFacts]
    upstream: Dict[int, List[int]]
    findings: Dict[str, List[Finding]]


# -- memo: every dataflow rule reads one propagation per analyze() call
# (thread-local, like the eval mode: concurrent analyses must not see
# each other's plans)

def propagate(plan, config) -> PlanFacts:
    """One topological walk over (plan, config); memoized on identity so
    the six dataflow rules share a single interpretation."""
    memo = getattr(_STATE, "memo", None)
    mode = _eval_chains_enabled()
    if (memo is not None and memo[0] is plan and memo[1] is config
            and memo[2] == mode):
        return memo[3]
    facts = _propagate(plan, config)
    _STATE.memo = (plan, config, mode, facts)
    return facts


def clear_memo() -> None:
    """Drop this thread's propagation memo (tests measuring a fresh
    submit-shaped pass use this)."""
    _STATE.memo = None


# -- schema plane -----------------------------------------------------------

def _source_schema(source) -> Schema:
    try:
        s = source.declared_schema()
    except Exception:
        return None
    if not isinstance(s, dict) or not s:
        return None
    return {str(k): str(v) for k, v in s.items()}


def _empty_batch(schema: Dict[str, str]):
    data = {f: np.zeros((0,), dtype=np.dtype(dt))
            for f, dt in schema.items()}
    return data, np.zeros((0,), np.int64), np.zeros((0,), bool)


def _eval_chain(nf: NodeFacts, fns, schema: Dict[str, str],
                out: Dict[str, List[Finding]]) -> Schema:
    """Abstractly evaluate a chain's fused fns on an EMPTY typed batch.
    A KeyError with a string key is exactly the field-reference error
    FIELD_NOT_IN_SCHEMA exists for; anything else degrades to unknown
    (the fn is opaque to this analysis, not wrong)."""
    data, ts, valid = _empty_batch(schema)
    for i, fn in enumerate(fns):
        known = sorted(data)
        try:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                with np.errstate(all="ignore"):
                    data, ts, valid = fn(data, ts, valid)
            data = {str(k): np.asarray(v) for k, v in dict(data).items()}
        except KeyError as e:
            missing = e.args[0] if e.args else "?"
            # only a STRING key ABSENT from the input schema is a
            # field-reference error; a KeyError whose key IS in the
            # schema came from some other dict inside the fn (a
            # runtime-populated lookup table) — that fn is opaque to
            # this analysis, not wrong
            if isinstance(missing, str) and missing not in known:
                out["FIELD_NOT_IN_SCHEMA"].append(_f(
                    f"chain {nf.name!r} (fn {i}) references field "
                    f"{missing!r}, which is not in its input schema "
                    f"{known} — this map/filter raises KeyError on the "
                    "first batch",
                    fix="emit the field upstream (or fix the name); "
                        "`analyze --explain` prints each node's "
                        "inferred schema",
                    node=nf.node_id, node_name=nf.name))
                nf.schema_note = f"fn {i} references missing {missing!r}"
            else:
                nf.schema_note = f"fn {i} raised KeyError({missing!r})"
            return None
        except Exception as e:
            nf.schema_note = (f"fn {i} opaque to abstract eval "
                              f"({type(e).__name__})")
            return None
    return {k: str(v.dtype) for k, v in data.items()}


def _check_fields(nf: NodeFacts, schema: Schema, fields, what: str,
                  out: Dict[str, List[Finding]]) -> None:
    """FIELD_NOT_IN_SCHEMA for declared op field references (key
    columns, aggregate input lanes, join keys) against a KNOWN input
    schema. Unknown schema = no finding (conservative)."""
    if schema is None:
        return
    for f in fields:
        if f and f not in schema:
            out["FIELD_NOT_IN_SCHEMA"].append(_f(
                f"{nf.kind} {nf.name!r} {what} {f!r}, but the upstream "
                f"schema is {sorted(schema)} — the field was dropped or "
                "renamed before this operator",
                fix="carry the field through the upstream maps, or fix "
                    "the reference; `analyze --explain` prints each "
                    "node's inferred schema",
                node=nf.node_id, node_name=nf.name))


# -- state plane ------------------------------------------------------------

def _lane_bytes(agg) -> int:
    """Per-(key, cell) accumulator footprint of the dense lane layout:
    f32 sum/max/min lanes (integer lanes at their own widths) + the
    always-present i64 count lane."""
    if getattr(agg, "lane_dtypes", None) is not None:
        return sum(8 if dt == "int64" else 4
                   for fam in agg.lane_dtypes for dt in fam) + 8
    return (agg.sum_width + agg.max_width + agg.min_width) * 4 + 8


def _is_purging(trigger) -> bool:
    from flink_tpu.api.windowing import PurgingTrigger

    return isinstance(trigger, PurgingTrigger)


def _state_facts(node, config) -> Tuple[str, str, Optional[int]]:
    """(bound, detail, bytes_per_key) for one stateful node — window
    type, trigger/evictor discipline, session gap, and CEP skip
    strategy decide the bound; the dense layouts get a bytes estimate
    from the window/lateness geometry."""
    from flink_tpu.api.windowing import GlobalWindows

    wt = node.window_transform
    kind = node.kind
    if kind in ("window", "window_all"):
        assigner = getattr(wt, "assigner", None)
        lat = int(getattr(wt, "allowed_lateness_ms", 0))
        if isinstance(assigner, GlobalWindows):
            trig = getattr(wt, "trigger", None)
            if trig is None:
                return ("unbounded",
                        "GlobalWindows with no trigger: every record is "
                        "state forever", None)
            if _is_purging(trig):
                return ("bounded", "GlobalWindows purged at every fire",
                        _lane_bytes(wt.aggregate))
            return ("unbounded",
                    f"GlobalWindows with non-purging "
                    f"{type(trig).__name__}: accumulators are never "
                    "cleared", None)
        pane = int(assigner.pane_ms)
        live = (int(assigner.size_ms) + lat + pane - 1) // pane + 1
        per = _lane_bytes(wt.aggregate)
        return ("bounded",
                f"keys × {live} live panes (window {assigner.size_ms}ms"
                f" + lateness {lat}ms / pane {pane}ms), "
                f"{per} B per (key, pane) cell", per * live)
    if kind == "evicting_window":
        assigner = getattr(wt, "assigner", None)
        trig = getattr(wt, "trigger", None)
        if isinstance(assigner, GlobalWindows) and not _is_purging(trig) \
                and getattr(wt, "evictor", None) is None:
            return ("unbounded",
                    "GlobalWindows element buffer with a non-purging "
                    f"trigger ({type(trig).__name__ if trig else 'none'})"
                    " and no evictor: the buffer retains every element "
                    "forever", None)
        return ("bounded",
                "element buffer within window lifetime + lateness "
                "(bytes are data-dependent)", None)
    if kind == "count_window":
        if getattr(wt, "purge", True):
            return ("bounded",
                    f"one accumulator per key, purged every "
                    f"{getattr(wt, 'size', '?')} elements",
                    _lane_bytes(wt.aggregate))
        return ("unbounded",
                "count window without purge: accumulators never reset",
                None)
    if kind == "session":
        per = _lane_bytes(wt.aggregate) + 24  # + key/start/last i64
        return ("bounded",
                f"live spans expire at the watermark horizon (gap "
                f"{wt.gap_ms}ms + lateness "
                f"{getattr(wt, 'allowed_lateness_ms', 0)}ms), "
                f"{per} B per span", per)
    if kind == "global_agg":
        return ("bounded",
                "one accumulator per key, never expires — bounded by "
                "key cardinality (state.num-key-shards × "
                "state.slots-per-shard)", _lane_bytes(wt.aggregate))
    if kind == "keyed_join":
        return ("bounded",
                "both sides kept per key, never expires (no window, no "
                "TTL) — bounded by key cardinality (state.num-key-shards "
                "× state.slots-per-shard); 9 int32 words a slot on the "
                "device lane", 36)
    if kind == "join":
        return ("bounded",
                "both sides buffered within window lifetime + lateness "
                "(bytes are data-dependent)", None)
    if kind == "cep":
        pattern = getattr(wt, "pattern", None)
        mode = getattr(pattern, "after_match_mode", "SKIP_PAST_LAST_EVENT")
        stages = getattr(pattern, "stages", None) or ()
        detail = (f"partial-match state per key "
                  f"({len(stages) or '?'} stages, {mode})")
        if mode == "NO_SKIP":
            detail += (" — bounded overflow-checked buffer of "
                       "overlapping partial matches")
        return ("bounded", detail, None)
    if kind == "async_io":
        return ("bounded",
                f"≤ {getattr(wt, 'capacity', '?')} in-flight batches",
                None)
    if kind == "process":
        return ("opaque", "user-managed keyed state + timers", None)
    if kind == "broadcast_connect":
        return ("opaque", "user-managed broadcast state", None)
    return ("stateless", "", None)


# -- watermark plane --------------------------------------------------------

def _wm_facts(node, in_wm: List[str]) -> Tuple[str, str]:
    """(axis, note) of a node's OUTPUT rows. The stepping rules follow
    the driver's fired-row forwarding: downstream ts is ``__ts__`` if
    the op emits one, else ``window_end - 1`` (runtime/driver.py
    _emit_fired) — so the axis is the op's window axis."""
    from flink_tpu.api.windowing import GlobalWindows

    kind = node.kind
    if kind == "source":
        s = node.watermark_strategy
        if s is None:
            return "event", "default monotonous clock"
        note = f"bounded-out-of-orderness {s.max_out_of_orderness_ms}ms"
        if s.idleness_ms is not None:
            note += f", idle after {s.idleness_ms}ms"
        return "event", note
    if kind in ("window", "window_all", "evicting_window"):
        assigner = getattr(node.window_transform, "assigner", None)
        if isinstance(assigner, GlobalWindows):
            return "none", ("global windows: fired rows carry the "
                            "eternal window end, not event time")
        if not bool(getattr(assigner, "is_event_time", True)):
            return "processing", ("rows stamped off the operator clock, "
                                  "not the source watermark")
        return "event", "fired at the event watermark"
    if kind == "count_window":
        return "none", ("count windows are event-time-blind: fired rows "
                        "carry the eternal window end")
    if kind in ("session", "cep", "join"):
        return "event", "fired at the event watermark"
    if kind == "global_agg":
        return "event", "upsert rows stamped at the emission watermark"
    if kind == "keyed_join":
        return "event", ("changelog rows stamped with their key's newest "
                         "event time in the mini-batch")
    # chains/partitions/unions/sinks/async_io/broadcast: pass-through
    if not in_wm:
        return "event", ""
    if all(w == "event" for w in in_wm):
        return "event", ""
    off = next(w for w in in_wm if w != "event")
    return off, "inherited from a non-event-time input leg"


# -- the walk ---------------------------------------------------------------

def _propagate(plan, config) -> PlanFacts:
    from flink_tpu.api.sources import source_is_bounded

    try:
        from flink_tpu.log.connectors import LogSource
    except Exception:  # pragma: no cover - log plane not importable
        LogSource = ()  # type: ignore[assignment]

    upstream: Dict[int, List[int]] = {nid: [] for nid in plan.nodes}
    for n in plan.nodes.values():
        for d in n.downstream:
            upstream[d].append(n.id)

    out: Dict[str, List[Finding]] = {r: [] for r in _WALK_RULES}
    facts: Dict[int, NodeFacts] = {}

    for nid in plan.topo_order:
        node = plan.nodes[nid]
        ups = [facts[u] for u in upstream[nid]]
        nf = NodeFacts(node_id=nid, kind=node.kind, name=node.name)
        nf.log_tainted = any(u.log_tainted for u in ups)
        nf.bounded_input = all(u.bounded_input for u in ups)
        nf.in_schema = ups[0].schema if len(ups) == 1 else None
        nf.wm, nf.wm_note = _wm_facts(node, [u.wm for u in ups])
        nf.state, nf.state_detail, nf.state_bytes_per_key = \
            _state_facts(node, config)

        if node.kind == "source":
            nf.schema = _source_schema(node.source)
            nf.schema_note = ("declared" if nf.schema is not None
                              else "no declared schema")
            nf.log_tainted = isinstance(node.source, LogSource)
            try:
                nf.bounded_input = source_is_bounded(node.source)
            except Exception:
                nf.bounded_input = True
        elif node.kind == "chain":
            if nf.in_schema is None:
                nf.schema = None
                nf.schema_note = ups[0].schema_note if ups else ""
            elif not _eval_chains_enabled():
                nf.schema = None
                nf.schema_note = ("user fns not evaluated at submit — "
                                  "run `flink_tpu analyze` for full "
                                  "schema facts")
            else:
                nf.schema = _eval_chain(nf, node.fns, nf.in_schema, out)
                if nf.schema is not None:
                    nf.schema_note = "inferred (abstract eval)"
        elif node.kind == "union":
            known = [u for u in ups if u.schema is not None]
            if len(known) == len(ups) and ups:
                sets = [frozenset(u.schema) for u in known]
                if len(set(sets)) > 1:
                    legs = "; ".join(
                        f"node {u.node_id} ({u.name!r}): "
                        f"{sorted(u.schema)}" for u in known)
                    out["SCHEMA_MISMATCH_UNION"].append(_f(
                        f"union {node.name!r} merges streams with "
                        f"different field sets — {legs} — downstream "
                        "field references crash on one leg's batches",
                        fix="project both legs to one schema (map) "
                            "before the union",
                        node=nid, node_name=node.name))
                    nf.schema = None
                    nf.schema_note = "leg schemas disagree"
                else:
                    nf.schema = dict(known[0].schema)
                    dt = [u for u in known
                          if u.schema != known[0].schema]
                    nf.schema_note = ("merged"
                                      if not dt else
                                      "merged (leg dtypes differ)")
            else:
                nf.schema = None
                nf.schema_note = "a leg's schema is unknown"
        elif node.kind == "join":
            wt = node.window_transform
            lf = facts.get(node.left_input)
            rf = facts.get(node.right_input)
            if lf is not None:
                _check_fields(nf, lf.schema,
                              (wt.left_key,) + tuple(wt.left_fields),
                              "reads left-side field", out)
            if rf is not None:
                _check_fields(nf, rf.schema,
                              (wt.right_key,) + tuple(wt.right_fields),
                              "reads right-side field", out)
            nf.schema = node.out_schema
            nf.schema_note = "declared by the lowering" if nf.schema else ""
        elif node.kind == "keyed_join":
            wt = node.window_transform
            _check_fields(nf, nf.in_schema, (
                wt.side_field, wt.left_key, wt.right_key, wt.until_field,
                wt.carry_field, wt.value_field), "joins over field", out)
            nf.schema = node.out_schema
            nf.schema_note = "declared by the lowering"
        elif node.kind in ("window", "evicting_window", "count_window",
                           "session", "process", "cep", "global_agg"):
            # the keyBy exchange folds into the op; whether the key
            # column exists is a schema fact either way
            _check_fields(nf, nf.in_schema, [node.key_field],
                          "keys by field", out)
            agg = getattr(node.window_transform, "aggregate", None)
            # the event time is the operator's to hand a lane, no column
            agg_fields = [f for f in getattr(agg, "fields", None) or ()
                          if f != EVENT_TIME_FIELD]
            if agg_fields:
                _check_fields(nf, nf.in_schema, agg_fields,
                              "aggregates over field", out)
            nf.schema = node.out_schema
            nf.schema_note = "declared by the lowering" if nf.schema else ""
        elif node.kind == "window_all":
            agg = getattr(node.window_transform, "aggregate", None)
            agg_fields = getattr(agg, "fields", None)
            if agg_fields:
                _check_fields(nf, nf.in_schema, agg_fields,
                              "aggregates over field", out)
            nf.schema = node.out_schema
            nf.schema_note = "declared by the lowering" if nf.schema else ""
        elif node.kind in ("async_io", "broadcast_connect"):
            nf.schema = None
            nf.schema_note = "user fn output not modeled"
        else:  # partition, sink: pass-through
            nf.schema = ups[0].schema if ups else None
            nf.schema_note = ups[0].schema_note if ups else ""

        # changelog axis: retract-mode ops MINT op-typed output;
        # pass-through nodes carry it; every other stateful operator
        # emits fresh fired rows (the axis resets there — a window agg
        # over changelog input FOLDS the retractions, it does not
        # forward them)
        wt = getattr(node, "window_transform", None)
        if (node.kind in ("global_agg", "session")
                and getattr(wt, "retract", False)) \
                or node.kind == "keyed_join":
            nf.changelog = True
        elif node.kind in ("chain", "partition", "union", "sink"):
            nf.changelog = any(u.changelog for u in ups)
            if (nf.changelog and node.kind == "chain"
                    and nf.schema is not None and "__op__" not in nf.schema):
                nf.changelog = False  # a map projected the op column away
        facts[nid] = nf

    return PlanFacts(nodes=facts, upstream=upstream, findings=out)


# -- the dataflow rule catalog ----------------------------------------------

@plan_rule("FIELD_NOT_IN_SCHEMA", "error", plane="dataflow",
           fix="carry the field through upstream maps, or fix the name")
def field_not_in_schema(plan, config) -> Iterable[Finding]:
    """A keyBy / aggregate / join / chain references a field that no
    longer exists in its input schema (dropped or renamed upstream) —
    a guaranteed KeyError or wrong-column partitioning at runtime,
    caught by propagating source-declared schemas through the plan."""
    return propagate(plan, config).findings["FIELD_NOT_IN_SCHEMA"]


@plan_rule("SCHEMA_MISMATCH_UNION", "error", plane="dataflow",
           fix="project both legs to one schema before the union")
def schema_mismatch_union(plan, config) -> Iterable[Finding]:
    """A union merges streams whose field sets disagree: batches flow
    through alternately, so every downstream field reference crashes on
    one leg's batches (or silently reads a column that is sometimes
    absent)."""
    return propagate(plan, config).findings["SCHEMA_MISMATCH_UNION"]


@plan_rule("UNBOUNDED_STATE_GROWTH", "error", plane="dataflow",
           fix="use a purging trigger / evictor, or bound the window")
def unbounded_state_growth(plan, config) -> Iterable[Finding]:
    """A stateful operator whose state can only grow — a GlobalWindows
    buffer with a non-purging trigger, a count window that never purges
    — fed by an UNBOUNDED source in streaming mode: the job leaks until
    the state backend fails. (Bounded inputs cap state at end-of-input
    and stay silent; batch mode is re-execution and is skipped.)"""
    from flink_tpu.config import ExecutionOptions

    mode = str(config.get(ExecutionOptions.RUNTIME_MODE)).strip().lower()
    if mode == "batch":
        return
    for nf in propagate(plan, config).nodes.values():
        if nf.state == "unbounded" and not nf.bounded_input:
            yield _f(
                f"{nf.kind} {nf.name!r} has unbounded state growth "
                f"({nf.state_detail}) and is fed by an unbounded "
                "source — state grows until the backend fails",
                fix="purge at fire (PurgingTrigger / count_window), "
                    "set an evictor, or use a time-bounded assigner",
                node=nf.node_id, node_name=nf.name)


@plan_rule("STALLED_WATERMARK_LEG", "error", plane="dataflow",
           fix="feed event-time operators from event-time legs only")
def stalled_watermark_leg(plan, config) -> Iterable[Finding]:
    """An event-time operator fed by a leg whose rows carry no event
    time (processing-time windows, count/global windows): the pipeline
    watermark advances from SOURCE event timestamps, so the panes this
    leg's rows land in are never meaningfully crossed — the operator
    sits on its state forever (or fires garbage windows)."""
    from flink_tpu.analysis.plan_rules import (
        _EVENT_TIME_KINDS, _is_event_time)

    facts = propagate(plan, config)
    for nf in facts.nodes.values():
        node = plan.nodes[nf.node_id]
        if node.kind not in _EVENT_TIME_KINDS or not _is_event_time(node):
            continue
        for u in facts.upstream[nf.node_id]:
            uf = facts.nodes[u]
            if uf.wm != "event":
                axis = ("no time axis" if uf.wm == "none"
                        else "the processing-time axis")
                yield _f(
                    f"event-time {nf.kind} {nf.name!r} is fed by node "
                    f"{u} ({uf.name!r}), whose rows carry {axis} "
                    f"({uf.wm_note}) — the source-driven event "
                    "watermark can never meaningfully cross this leg's "
                    "windows",
                    fix="keep the leg on event time, or switch this "
                        "operator to a processing-time assigner",
                    node=nf.node_id, node_name=nf.name)


@plan_rule("NON_TXN_SINK_IN_CHAIN", "error", plane="dataflow",
           fix="use a TwoPhaseCommitSink on log-chained paths")
def non_txn_sink_in_chain(plan, config) -> Iterable[Finding]:
    """A job reading a durable-log topic (LogSource — the exactly-once
    job-chaining plane, PR 3) writes through a NON-transactional sink
    while checkpointing: a recovery replays the un-checkpointed tail
    into the sink, silently breaking the end-to-end exactly-once chain
    the upstream job's 2PC commit paid for. Escalates the generic
    NON_TRANSACTIONAL_SINK warning to an error on tainted paths."""
    from flink_tpu.api.sinks import sink_is_transactional
    from flink_tpu.config import CheckpointingOptions

    if config.get(CheckpointingOptions.INTERVAL) <= 0:
        return
    facts = propagate(plan, config)
    for nf in facts.nodes.values():
        node = plan.nodes[nf.node_id]
        if node.kind != "sink" or node.sink is None or not nf.log_tainted:
            continue
        if not sink_is_transactional(node.sink):
            yield _f(
                f"sink {nf.name!r} ({type(node.sink).__name__}) is "
                "downstream of a "
                "LogSource but not transactional — recovery replays the "
                "un-checkpointed tail into it, breaking the end-to-end "
                "exactly-once chain the upstream job's 2PC commit "
                "established",
                fix="use a TwoPhaseCommitSink (LogSink, FileSink, "
                    "TransactionalCollectSink) on log-chained paths",
                node=nf.node_id, node_name=nf.name)


@plan_rule("CHANGELOG_SINK_MISMATCH", "error", plane="dataflow",
           fix="use a changelog-capable sink (RetractSink / UpsertSink)")
def changelog_sink_mismatch(plan, config) -> Iterable[Finding]:
    """A retract-producing operator (retract-mode GROUP BY / session
    aggregation) feeds an append-only sink: the sink appends -U/+U
    pairs as if they were independent inserts, so every key update
    lands TWICE and the materialized result silently double-counts —
    the op-typed rows only mean something to a sink that folds them
    (``Sink.changelog_capable``)."""
    facts = propagate(plan, config)
    for nf in facts.nodes.values():
        node = plan.nodes[nf.node_id]
        if node.kind != "sink" or node.sink is None or not nf.changelog:
            continue
        if not getattr(node.sink, "changelog_capable", False):
            yield _f(
                f"sink {nf.name!r} ({type(node.sink).__name__}) receives "
                "an op-typed changelog stream (a retract-mode aggregation "
                "is upstream) but is append-only — every -U/+U update "
                "pair is appended as two inserts, silently "
                "double-counting each key update",
                fix="materialize through a changelog-capable sink "
                    "(RetractSink, UpsertSink) or drop retract mode if "
                    "append semantics are intended",
                node=nf.node_id, node_name=nf.name)


@plan_rule("STATE_BYTES_EXCEEDED", "warn", plane="dataflow",
           fix="shrink the window/lateness geometry or raise the budget")
def state_bytes_exceeded(plan, config) -> Iterable[Finding]:
    """A stateful operator's statically-estimated per-key state
    footprint (lane accumulators × live panes from the window/lateness
    geometry — the number `analyze --explain` prints) exceeds the
    configured ``analysis.max-state-bytes-per-key`` budget — the
    admission-control check for jobs sharing a chip's HBM. Off by
    default (budget 0)."""
    from flink_tpu.config import AnalysisOptions

    try:
        budget = int(config.get(AnalysisOptions.MAX_STATE_BYTES_PER_KEY))
    except (TypeError, ValueError):
        budget = 0
    if budget <= 0:
        return
    for nf in propagate(plan, config).nodes.values():
        est = nf.state_bytes_per_key
        if est is not None and est > budget:
            yield _f(
                f"{nf.kind} {nf.name!r} holds an estimated {est} B of "
                f"state per key ({nf.state_detail}), over the "
                f"analysis.max-state-bytes-per-key budget of {budget} B",
                fix="shrink window size / lateness / lane count, or "
                    "raise the budget",
                node=nf.node_id, node_name=nf.name)


# -- explain ----------------------------------------------------------------

def _fmt_schema(schema: Schema, note: str) -> str:
    if schema is None:
        return f"unknown ({note})" if note else "unknown"
    body = ", ".join(f"{k}:{schema[k]}" for k in sorted(schema))
    return "{" + body + "}" + (f" ({note})" if note else "")


def explain_plan(plan, config) -> str:
    """Per-node inferred facts of the propagated lattices — the
    `analyze --explain` surface. One block per node in topological
    order: output schema, watermark axis, state bound (+ bytes-per-key
    estimate where the layout is dense)."""
    facts = propagate(plan, config)
    lines = ["per-node dataflow facts (schema | watermark | state):"]
    for nid in plan.topo_order:
        nf = facts.nodes[nid]
        state = nf.state
        if nf.state_detail:
            state += f" [{nf.state_detail}]"
        if nf.state_bytes_per_key is not None:
            state += f" ~{nf.state_bytes_per_key} B/key"
        wm = nf.wm + (f" ({nf.wm_note})" if nf.wm_note else "")
        if nf.changelog:
            wm += " | changelog (op-typed rows)"
        lines.append(f"node {nid} {nf.kind} {nf.name!r}:")
        lines.append(f"  schema    {_fmt_schema(nf.schema, nf.schema_note)}")
        lines.append(f"  watermark {wm}")
        lines.append(f"  state     {state}")
    return "\n".join(lines)
