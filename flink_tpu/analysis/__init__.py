"""Static analysis — catch correctness bugs before the first record flows.

Three planes (ref: the validation pass of Flink's StreamGraph
translation — StreamGraphGenerator / StreamingJobGraphGenerator reject
malformed graphs at compile time, SURVEY §3.2; bounded-execution
validation, §3.6 — generalized into a rule engine):

- **Plan analysis** (``plan_rules.py``): linear rules over a lowered
  ``ExecutionPlan`` + its ``Configuration`` — misconfigurations that
  would otherwise fail minutes into a run (unbounded source in batch
  mode, two writers on one log topic, fault rules that match nothing)
  or silently corrupt results (event-time windows with no watermark
  strategy, non-transactional sinks under exactly-once). The driver
  runs every plane automatically at submit (``analysis.fail-on``);
  ``python -m flink_tpu analyze`` runs them standalone.

- **Dataflow analysis** (``dataflow.py``): ONE topological abstract
  interpretation propagating three lattices edge-by-edge — record
  schema (source declarations + compiler-recorded op schemas + abstract
  evaluation of chain fns on empty typed batches), state-growth bounds
  (bounded-by-geometry with a bytes-per-key estimate vs unbounded, from
  assigner/trigger/evictor/gap/skip-strategy facts), and watermark
  capability (event / processing / no time axis per leg). The dataflow
  rules (field-not-in-schema, union mismatch, unbounded growth, stalled
  legs, exactly-once taint through log topics, state budgets) read the
  propagated facts; ``analyze --explain`` prints them per node.

- **Repo AST lints** (``pylints.py`` over ``callgraph.py``): a
  pure-stdlib INTERPROCEDURAL pass over the codebase itself — the
  linted files are indexed into one project-wide call graph (defs,
  methods resolved through the receiver's inferred self-type,
  module-qualified calls, lock/lease binding types) and the protocol
  rules walk its edges: tracer leaks in jit kernels (host conversions /
  Python branches on traced values, followed through the helpers the
  traced arguments flow into — the failure class the kernels' design
  rules exist to prevent), fault-point drift in BOTH directions
  (unknown ``faults.fire`` literals and registered points nothing
  fires), config/metric name drift, unlocked shared-state writes in
  HostPool task closures at any call depth (lock guards recognized by
  binding type), raw durable writes bypassing the fs.py seam,
  lock-order (ABBA) cycles with both acquisition paths named, and
  fenced-record publications a deposed leaseholder could still make
  (no lease verify()/renew on the path). Run via ``python -m flink_tpu
  lint [--plane NAME]`` or ``tools/lint.py``; the dogfood gate
  (tests/test_analysis.py) keeps the shipped tree at zero findings and
  the full pass under a 3 s wall-clock budget.

RULES.md is GENERATED from the registrations (``docs.py`` +
``tools/gen_rules.py``) with a tier-1 staleness gate, so a rule cannot
ship undocumented.

Honest scope: the dataflow plane has no cross-function taint (a field
smuggled through opaque user state is invisible), no symbolic shapes
(state estimates use declared config geometry, not data), and schema
facts stop at the first chain that is opaque to empty-batch
evaluation. The repo lints DO cross functions, but the walks are
capped (8 call hops for tracer taint, 6 for pool writes and fence
walks), only name / self-method / module-qualified calls resolve (no
duck-typed dispatch), and lock identity is syntactic — a lock aliased
through a variable or passed as a bare parameter falls back to
name-substring recognition.
"""
from flink_tpu.analysis.core import (
    AnalysisError,
    Finding,
    analyze,
    analyze_config,
    render_findings,
)

__all__ = ["AnalysisError", "Finding", "analyze", "analyze_config",
           "render_findings"]
