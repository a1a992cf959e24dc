"""ExecutionPlan IR — the compiled program's runtime schedule.

The paper's end goal is transparent acceleration of whole *programs*
encapsulated behind an MPI implementation (§VI.A), not of single
collectives.  A program-level runtime therefore needs more than an eager
stage chain: it needs to know which stages *depend* on each other and
which are free to overlap — SwitchML-style aggregation and ACCL+ both
win by streaming independent transfers through the fabric concurrently.

This module is that layer.  :func:`build_plan` derives explicit
dependency edges between emitted stages from the DAG's value ids and
groups independent stages into concurrent **waves** (Kahn levels):
every stage in wave *w* depends only on stages in waves < *w*, so a
runtime may launch a whole wave at once.  Within a wave the plan further
partitions stages into per-axis **dispatch groups** (``wave_groups``):
stages sharing a mesh axis contend for that axis's rings and must
serialize; stages on different axes traverse disjoint links and are free
to run concurrently.  Three consumers share the IR:

  * :meth:`repro.core.compiler.CompiledProgram.__call__` executes the
    plan wave by wave through :func:`execute`.  In overlapped mode the
    wave's dispatch groups are issued round-robin into one merged
    region: same-axis stages are tied together with explicit
    ``lax.optimization_barrier`` edges (pinning the ring order in the
    emitted HLO, so every rank issues the axis's collectives
    identically), while cross-axis stages carry **no** ordering edges —
    XLA's async scheduler may start their collectives concurrently.
    Serial mode (``overlapped=False``) reproduces the strict
    stage-ordered emission for A/B measurement.
  * :func:`repro.core.netmodel.program_time` costs the plan as a
    critical path with a per-tier overlap fraction instead of a
    sum of stage times,
  * :class:`repro.cgra.simulate.SwitchSim` advances its per-rank clocks
    wave by wave, overlapping stages that traverse *different* mesh
    axes (disjoint links, shared injection ports) and serializing
    stages that share one — the measurement that calibrates the
    analytic overlap model.

:func:`execute` also threads persistent **bucket arenas** through the
plan: a stage carrying an ``arena_slot`` (the Coalesce bucket packs)
receives its pre-allocated flat buffer and writes leaves into it in
place; the written buffers are returned alongside the program outputs so
a caller can donate them back on the next step
(``jax.jit(..., donate_argnums=...)``), dropping the pack transient from
2× to ~1× bucket size.

The plan is deliberately dumb data (stage indices + edges + waves): it
duck-types against anything carrying ``in_vids``/``out_vids``, so the
cost model can consume it without importing the compiler.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence

from repro.obs import metrics as _metrics
from repro.obs import spans as _spans

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Dependency-explicit schedule over a compiled program's stages.

    ``deps[i]`` are the stage indices stage *i* consumes values from;
    ``waves`` partitions ``range(len(stages))`` into concurrency groups
    in topological order; ``wave_groups[w]`` splits wave ``w`` into
    per-axis dispatch groups ``(axis, stage_indices)`` — stages within a
    group share a mesh axis (or are axis-less local compute) and
    serialize, groups are mutually independent.  ``stages`` is the same
    sequence the owning ``CompiledProgram`` holds (kept here so the cost
    model and the simulator can walk the plan alone).
    """

    stages: tuple
    num_inputs: int
    outputs: tuple[int, ...]
    deps: tuple[tuple[int, ...], ...]
    waves: tuple[tuple[int, ...], ...]
    wave_groups: tuple[tuple[tuple[str, tuple[int, ...]], ...], ...] = ()

    @property
    def n_stages(self) -> int:
        return len(self.stages)

    @property
    def n_waves(self) -> int:
        return len(self.waves)

    def wave_of(self, stage_index: int) -> int:
        for w, group in enumerate(self.waves):
            if stage_index in group:
                return w
        raise IndexError(stage_index)

    def validate(self) -> None:
        """Every stage appears in exactly one wave, strictly after all of
        its dependencies' waves; wave_groups re-partition each wave."""
        seen: dict[int, int] = {}
        for w, group in enumerate(self.waves):
            for i in group:
                if i in seen:
                    raise ValueError(f"stage {i} scheduled twice")
                seen[i] = w
        if len(seen) != len(self.stages):
            raise ValueError("waves do not cover every stage")
        for i, ds in enumerate(self.deps):
            for d in ds:
                if seen[d] >= seen[i]:
                    raise ValueError(
                        f"stage {i} (wave {seen[i]}) depends on stage {d} "
                        f"(wave {seen[d]}) — waves are not topological")
        for wave, groups in zip(self.waves, self.dispatch_groups()):
            flat = sorted(i for _, idxs in groups for i in idxs)
            if flat != sorted(wave):
                raise ValueError(
                    f"wave_groups {groups} do not partition wave {wave}")

    def dispatch_groups(self) -> tuple:
        """The per-wave axis dispatch groups — the stored ``wave_groups``
        when present, else derived on the fly (a plan built by hand with
        just stages/waves still dispatches correctly instead of silently
        running nothing)."""
        if len(self.wave_groups) == len(self.waves):
            return self.wave_groups
        return tuple(_axis_groups(self.stages, w) for w in self.waves)


def _axis_groups(stages: Sequence,
                 wave: tuple[int, ...]) -> tuple[tuple[str, tuple[int, ...]],
                                                 ...]:
    """Partition one wave into per-axis dispatch groups.

    Stages sharing a (non-empty) axis contend for that axis's rings and
    form one serialized group, in plan order.  Axis-less stages (local
    maps) are each their own singleton group — nothing serializes free
    compute.

    Within an axis group, batched ring launches (``batched_allreduce``)
    are issued first: the merged ring is the group's long pole, and
    leading with it lets the leftover per-program launches hide behind
    it.  Stages within one wave are mutually independent (same Kahn
    level), so the stable reorder cannot break a dependency.
    """
    by_axis: dict[str, list[int]] = {}
    groups: list[tuple[str, tuple[int, ...]]] = []
    for i in wave:
        ax = getattr(stages[i], "axis", "")
        if not ax:
            groups.append(("", (i,)))
            continue
        if ax not in by_axis:
            by_axis[ax] = []
            groups.append((ax, by_axis[ax]))  # placeholder; fixed below
        by_axis[ax].append(i)

    def batched_first(idxs):
        return tuple(sorted(
            idxs,
            key=lambda i: getattr(stages[i], "kind", "")
            != "batched_allreduce"))

    return tuple((ax, batched_first(idxs) if isinstance(idxs, list)
                  else idxs)
                 for ax, idxs in groups)


def _pipeline_levels(stages: Sequence, deps: Sequence[tuple[int, ...]],
                     levels: list[int]) -> list[int]:
    """Software-pipeline same-axis collective chains.

    Two topology-preserving refinements over the plain Kahn (ASAP)
    levels — symmetric bucket chains (pack -> ring -> epilogue per
    bucket, all on one axis) otherwise schedule all packs together, all
    rings together and all epilogues together, so no map ever hides
    under a ring:

      * a wave whose collectives all share ONE axis serializes on that
        axis's rings anyway (zero concurrency) — the extras slide to
        later waves, staggering the chains.  Waves holding collectives
        on several axes are left alone: their cross-axis overlap is the
        thing the tier model rewards, and splitting them would forfeit
        it;
      * an axis-less stage (local compute) with a consumer slides down
        to the wave just before its earliest consumer, landing next to
        the staggered collective it can hide under.  Output maps keep
        their ASAP slot.
    """
    n = len(stages)

    def axis(i: int) -> str:
        return getattr(stages[i], "axis", "") or ""

    for _ in range(n):
        # re-settle the dependency floor (stage order is topological)
        for i in range(n):
            if deps[i]:
                levels[i] = max(levels[i],
                                1 + max(levels[d] for d in deps[i]))
        by_wave: dict[int, list[int]] = {}
        for i in range(n):
            if axis(i):
                by_wave.setdefault(levels[i], []).append(i)
        moved = False
        for lv in sorted(by_wave):
            idxs = by_wave[lv]
            if len(idxs) < 2 or len({axis(i) for i in idxs}) != 1:
                continue
            for i in idxs[1:]:
                levels[i] += 1
            moved = True
            break
        if not moved:
            break

    consumers: list[list[int]] = [[] for _ in range(n)]
    for i in range(n):
        for d in deps[i]:
            consumers[d].append(i)
    for i in range(n - 1, -1, -1):
        if axis(i) or not consumers[i]:
            continue
        tgt = min(levels[c] for c in consumers[i]) - 1
        if tgt > levels[i]:
            levels[i] = tgt

    # compress any emptied levels
    remap = {lv: w for w, lv in enumerate(sorted(set(levels)))}
    return [remap[lv] for lv in levels]


def build_plan(stages: Sequence, num_inputs: int,
               outputs: tuple[int, ...]) -> ExecutionPlan:
    """Derive the dependency edges and concurrency waves for ``stages``.

    A stage depends on the stage producing each of its input values;
    values below ``num_inputs`` are program inputs (no producer).  Wave
    assignment starts from the Kahn level (1 + the max level of any
    dependency) and is then refined by :func:`_pipeline_levels` to
    stagger same-axis collective chains.
    """
    producer: dict[int, int] = {}
    for i, st in enumerate(stages):
        for v in st.out_vids:
            if v in producer:
                raise ValueError(
                    f"value {v} produced by stage {producer[v]} and "
                    f"stage {i} — the stage list is not single-assignment")
            producer[v] = i
    deps: list[tuple[int, ...]] = []
    levels: list[int] = []
    for i, st in enumerate(stages):
        ds = sorted({producer[v] for v in st.in_vids if v in producer})
        deps.append(tuple(ds))
        levels.append(1 + max((levels[d] for d in ds), default=-1))
    levels = _pipeline_levels(stages, deps, levels)
    n_waves = (max(levels) + 1) if levels else 0
    waves = tuple(tuple(i for i, l in enumerate(levels) if l == w)
                  for w in range(n_waves))
    wave_groups = tuple(_axis_groups(stages, w) for w in waves)
    plan = ExecutionPlan(tuple(stages), num_inputs, tuple(outputs),
                         tuple(deps), waves, wave_groups)
    plan.validate()
    return plan


def _barrier_tie(prev_outs: tuple, ins: tuple) -> tuple:
    """Tie a stage's inputs to its same-axis predecessor's outputs with an
    ``optimization_barrier`` edge, pinning the axis's collective order in
    the emitted HLO.  Falls back to trace order on jax versions without
    the primitive."""
    from jax import lax

    barrier = getattr(lax, "optimization_barrier", None)
    if barrier is None or not prev_outs:      # pragma: no cover - old jax
        return ins
    tied = barrier(tuple(ins) + tuple(prev_outs))
    return tuple(tied[:len(ins)])


def _issue_order(groups) -> list[int]:
    """Round-robin across a wave's dispatch groups: the k-th stage of
    every axis group is issued before any group's (k+1)-th, so
    different-axis collectives sit adjacent in the merged region and
    XLA's async scheduler can start them together."""
    order: list[int] = []
    cursors = [list(idxs) for _, idxs in groups]
    while any(cursors):
        for c in cursors:
            if c:
                order.append(c.pop(0))
    return order


def execute(plan: ExecutionPlan, args: Sequence[PyTree], *,
            arenas: Optional[Sequence] = None,
            overlapped: bool = True,
            instrument: Optional[list] = None) -> tuple:
    """Run the plan over rank-local values, wave by wave.

    ``overlapped=True`` (the default) issues each wave as one merged
    region: same-axis stages are chained with explicit
    ``optimization_barrier`` edges (they contend for one ring — every
    rank must issue them in the same order), different-axis stages are
    interleaved round-robin with no ordering edges between them, so
    XLA's latency-hiding scheduler may run their collectives
    concurrently.  ``overlapped=False`` reproduces the strict
    stage-ordered serial emission (the pre-overlap runtime) for A/B
    comparison.

    ``arenas`` are the persistent flat buffers for the program's bucket
    packs (one per ``arena_slot``, see
    :meth:`repro.core.compiler.CompiledProgram.make_arenas`); each pack
    writes its leaves into its arena in place rather than concatenating
    into a fresh buffer.  When given, returns ``(outputs, new_arenas)``
    with the written buffers, so the caller can donate them back on the
    next call; otherwise returns just the output tuple.

    ``instrument`` is the stage-trace recorder hook: a list that receives
    one :class:`repro.obs.spans.StageSpan` per executed stage — the
    shared stage-record schema (= ``repro.tune.trace.StageTrace``), with
    ``t_start``/``t_end`` ``perf_counter`` timestamps taken around a
    ``block_until_ready`` on the stage's outputs and the stage's payload
    bytes / placement already attached.  Only
    meaningful when the plan runs eagerly — under ``jit``/``shard_map``
    tracing the timestamps measure trace time, not run time; use the
    interleaved harness in :mod:`repro.tune.trace` for jitted programs.
    Instrumented stages synchronize per stage, so the recorded run is a
    serial measurement even in overlapped dispatch mode.
    """
    import jax

    env: dict[int, PyTree] = dict(enumerate(args))
    new_arenas = list(arenas) if arenas is not None else None
    wave_of = {i: w for w, ws in enumerate(plan.waves) for i in ws}

    def run_stage(i: int, prev_outs: tuple) -> tuple:
        st = plan.stages[i]
        ins = tuple(env[v] for v in st.in_vids)
        if overlapped and prev_outs:
            ins = _barrier_tie(prev_outs, ins)
        slot = getattr(st, "arena_slot", None)
        if instrument is not None:
            import time

            jax.block_until_ready(ins)
            t0 = time.perf_counter()
        # the stage's device operations carry its identity (the index
        # and kind of its StageSpan) on the profiler's trace
        with jax.named_scope(f"acis.{getattr(st, 'kind', '')}.s{i}"):
            if slot is not None and new_arenas is not None:
                outs = st.run(ins, st.axis, arena=new_arenas[slot])
                new_arenas[slot] = outs[0]
            else:
                outs = st.run(ins, st.axis)
        if instrument is not None:
            jax.block_until_ready(outs)
            span = _spans.from_stage(st, i, wave_of.get(i, 0), t0,
                                     time.perf_counter())
            instrument.append(span)
            rec = _metrics.RECORDER
            if rec.enabled:
                rec.count("exec.instrumented_stages")
                rec.observe("exec.stage_s", span.duration)
        for vid, o in zip(st.out_vids, outs):
            env[vid] = o
        return outs

    for wave, groups in zip(plan.waves, plan.dispatch_groups()):
        if not overlapped:
            for i in wave:
                run_stage(i, ())
            continue
        last_outs: dict[str, tuple] = {}
        for i in _issue_order(groups):
            ax = plan.stages[i].axis
            prev = last_outs.get(ax, ()) if ax else ()
            outs = run_stage(i, prev)
            if ax:
                last_outs[ax] = outs
    outs = tuple(env[v] for v in plan.outputs)
    if new_arenas is not None:
        return outs, tuple(new_arenas)
    return outs
