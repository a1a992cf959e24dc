"""CollectiveEngine — the MPI-transparency layer.

The paper encapsulates ACiS inside an MPI implementation so applications
accelerate without source changes (§VI.A).  The framework analogue: model /
training code talks to a :class:`CollectiveEngine`; a config flag selects
which transport actually runs.  Engines:

  * ``xla``             — passive-network baseline (XLA built-ins)
  * ``acis``            — explicit ring/log-step schedules (Types 1-4)
  * ``acis_compressed`` — acis + Type 2/3 wire compression with error
                          feedback on the gradient-sync path
  * ``acis_hierarchical`` (+ ``_compressed``) — pod-aware two-level sync

`gradient_sync` operates on *pytrees of gradients* inside a shard_map-manual
region over the DP axes; everything else in the step (model-parallel math)
stays in GSPMD-auto land.  See train/step.py for the integration.

All ``acis*`` gradient syncs are one traced switch program — per leaf a
``reduce(axis="auto")`` (plus error-feedback target/residual maps on the
compressed backends) — compiled once through the Legalize → LowerTopology
→ Coalesce → FuseHops → SelectSchedule → Emit pipeline against the
engine's :class:`~repro.core.compiler.Topology` and cached per pytree
structure.  The hierarchical RS/AR/AG schedule is no longer a call-site
convention: it is what LowerTopology emits for a multi-axis reduce — and
the per-leaf collectives are not what actually runs: the Coalesce pass
buckets compatible leaves into flat-buffer bucket collectives
(``CollectiveConfig.bucket_bytes``), so a many-leaf pytree syncs in a
few streaming buckets executed over an explicit
:class:`~repro.core.executor.ExecutionPlan`.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import collectives, compiler, tracing
from repro.core.lookaside import init_residual
from repro.core.types import ADD
from repro.obs import metrics as _obs

PyTree = Any

BACKENDS = ("xla", "acis", "acis_compressed", "acis_hierarchical",
            "acis_hierarchical_compressed")


def live_axis_sizes(axes, known: Optional[dict] = None) -> dict:
    """Best-effort ``{axis: size}`` for the named mesh axes.

    Sizes are read live via ``lax.axis_size`` — available when called
    inside a shard_map region manual over the axis — so compile paths
    can key their caches and feed the cost model without a mesh in
    hand.  ``known`` entries are kept as-is; axes not bound anywhere
    simply stay absent.
    """
    sizes = dict(known) if known else {}
    for ax in axes:
        if ax is None or ax in sizes:
            continue
        try:
            sizes[ax] = lax.axis_size(ax)
        except Exception:            # not under shard_map over this axis
            pass
    return sizes


@dataclasses.dataclass(frozen=True)
class RecompileReport:
    """What :meth:`CollectiveEngine.recompile` reused vs rebuilt.

    Shape-preserving topology deltas (rank dropout absorbed by the alive
    mask, ×k link degradation) must report 100% reuse: the mask is a
    runtime program input, so membership flips never retrace, and the
    arenas are keyed by compiled-program identity.
    """

    programs_reused: int = 0
    programs_rebuilt: int = 0
    arenas_reused: int = 0
    arenas_rebuilt: int = 0
    shape_preserving: bool = True

    @property
    def full_recompile(self) -> bool:
        return self.programs_rebuilt > 0

    @property
    def reuse_frac(self) -> float:
        total = (self.programs_reused + self.programs_rebuilt
                 + self.arenas_reused + self.arenas_rebuilt)
        if total == 0:
            return 1.0
        return (self.programs_reused + self.arenas_reused) / total


@dataclasses.dataclass(frozen=True)
class CollectiveConfig:
    backend: str = "xla"
    # wire codec for the compressed paths: int8 | bf16 | fp8
    codec: str = "int8"
    # compressor for error-feedback sync: int8 | topk
    compressor: str = "int8"
    topk_ratio: float = 0.01
    latency_optimal_below: int = 16384  # bytes; ring-vs-latency crossover
    # Coalesce bucket size (bytes): per-leaf reductions sharing an
    # axis/monoid/codec are concatenated into flat buckets of this many
    # bytes, one collective per bucket.  None = derive from the cost
    # model's crossover for the axis traversed
    # (repro.core.netmodel.bucket_bytes); 0 = disable bucketing.
    bucket_bytes: Optional[int] = None
    # switch CGRA the PlaceCGRA pass maps stage bodies onto; None = the
    # paper's Table II device (repro.cgra.device.PAPER_CGRA)
    cgra_device: Optional[Any] = None
    # overlapped wave dispatch (repro.core.executor.execute): same-axis
    # stages of a wave are chained with explicit optimization_barrier
    # edges, different-axis stages issue with no ordering edges so XLA
    # may run their collectives concurrently.  False = strict
    # stage-ordered serial emission (the pre-overlap runtime, kept for
    # A/B measurement).
    overlap_dispatch: bool = True
    # hoist a bucket's shared elementwise epilogue (the gradient mean)
    # to one bucket-sized kernel; False keeps per-leaf epilogues.  A
    # tunable: the hoist trades kernel count against wave-level overlap.
    epilogue_hoist: bool = True
    # route the bulk data path through the Pallas kernels (switchops
    # registry): the Coalesce bucket pack becomes one fused arena-aliased
    # launch and ring hop combines run the registered kernels.  A kernel
    # compiles (Mosaic) on an accelerator and interprets only on the CPU
    # backend (tier-1 numerics validation) — kernels/_interpret_default.
    # Default comes from $ACIS_USE_KERNELS (the CI kernels leg sets it).
    use_kernels: bool = dataclasses.field(
        default_factory=lambda: os.environ.get(
            "ACIS_USE_KERNELS", "") not in ("", "0"))
    # merge a wave's independent same-axis allreduces (plain elementwise
    # monoid, identity codec) into ONE ring over a chunk-aligned stacked
    # buffer — k ring launches collapse to one, amortizing the per-launch
    # hop latency.  Bit-compatible with per-program launches (each lane
    # keeps its chunk index, hence its fold order).  A tunable.
    batch_rings: bool = False
    # per-merged-launch payload cap in bytes for batch_rings; None =
    # the compiler default (a few MB), 0 = uncapped.  Bounds the
    # synchronization/cache cost of one giant stacked buffer while
    # still amortizing launches across small rings.
    batch_rings_bytes: Optional[int] = None
    # consult (and on a miss, populate) the on-disk tuning DB
    # (repro.tune.search) at compile: the stored winning overrides for
    # this (program structure, topology) are applied transparently.
    autotune: bool = False
    # tuning-DB path; None = $ACIS_TUNE_DB, else ./.acis_tune.json
    tune_db: Optional[str] = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(
                f"backend {self.backend!r} not in {BACKENDS}")

    def cache_key(self) -> tuple:
        """Every config field a compiled program's structure depends on.

        Compiled-program caches must include this in their keys: the
        autotuner varies ``bucket_bytes``/``overlap_dispatch``/
        ``epilogue_hoist``/``latency_optimal_below``, so a tuned
        program must not collide with the default config's cache entry
        for the same pytree structure.
        """
        return (self.backend, self.codec, self.compressor,
                self.topk_ratio, self.latency_optimal_below,
                self.bucket_bytes, self.overlap_dispatch,
                self.epilogue_hoist, self.use_kernels,
                self.batch_rings, self.batch_rings_bytes)


class CollectiveEngine:
    """Rank-local collective transport with backend dispatch."""

    def __init__(self, config: CollectiveConfig,
                 inner_axis: str = "data",
                 outer_axis: Optional[str] = None):
        self.config = config
        self.inner_axis = inner_axis
        self.outer_axis = outer_axis
        self._sync_cache: dict = {}   # pytree structure → CompiledProgram
        self._arena_cache: dict = {}  # CompiledProgram → bucket arenas
        self._tune_cache: dict = {}   # pytree structure → tuned config
        self._last_sync = None        # most recently built/fetched program

    # -- properties ---------------------------------------------------------

    @property
    def compressed(self) -> bool:
        return "compressed" in self.config.backend

    @property
    def hierarchical(self) -> bool:
        return "hierarchical" in self.config.backend

    @property
    def base_backend(self) -> str:
        return "xla" if self.config.backend == "xla" else "acis"

    def needs_residual(self) -> bool:
        return self.compressed

    def init_state(self, grads_like: PyTree) -> Optional[PyTree]:
        """Look-aside state (Type 3): error-feedback residuals, or None.

        Uncompressed backends are stateless — returning None (instead of a
        pytree of dead zero scalars) keeps checkpoints and donated buffers
        free of fake state."""
        if self.compressed:
            return init_residual(grads_like, jnp.float32)
        return None

    # -- topology (the compiler's view of this engine's DP axes) -------------

    def topology(self, mesh: Optional[jax.sharding.Mesh] = None, *,
                 axis_size=None) -> compiler.Topology:
        """The engine's DP axes as a compile :class:`~repro.core.compiler.
        Topology`: inner axis on the fast intra-pod tier, outer axis (when
        configured and present on the mesh) on the thin inter-pod tier.

        ``axis_size`` may be an int (the inner axis) or an {axis: size}
        mapping — pass the outer size too so SelectSchedule can cost the
        inter-pod stage against the thin DCI tier on mesh-less compiles.
        """
        sizes: dict = {}
        if isinstance(axis_size, dict):
            sizes.update(axis_size)
        elif axis_size is not None:
            sizes[self.inner_axis] = axis_size
        if mesh is not None:         # the mesh is authoritative
            sizes.update(zip(mesh.axis_names, mesh.devices.shape))
        axes = [compiler.AxisSpec(self.inner_axis,
                                  sizes.get(self.inner_axis), "ici")]
        if self.outer_axis is not None and \
                (mesh is None or self.outer_axis in mesh.axis_names):
            axes.append(compiler.AxisSpec(self.outer_axis,
                                          sizes.get(self.outer_axis), "dci"))
        return compiler.Topology(tuple(axes))

    # -- the gradient-sync transport -----------------------------------------

    def _local_alive(self, membership) -> jax.Array:
        """This rank's liveness flag (float32 scalar) from a membership
        view — a :class:`repro.elastic.Membership`, a length-``n_ranks``
        mask array (rank = ``outer_index * |inner| + inner_index``), or
        an already-rank-local scalar.  Indexed live via ``axis_index``,
        so the mask is runtime data: membership flips never retrace."""
        if hasattr(membership, "mask_array"):
            mask = jnp.asarray(membership.mask_array(jnp.float32))
        else:
            mask = jnp.asarray(membership, jnp.float32)
        if mask.ndim == 0:
            return mask.astype(jnp.float32)
        idx = lax.axis_index(self.inner_axis)
        if self.outer_axis is not None:
            try:
                idx = idx + lax.axis_size(self.inner_axis) \
                    * lax.axis_index(self.outer_axis)
            except Exception:    # outer axis configured but not on the mesh
                pass
        return mask.reshape(-1)[idx].astype(jnp.float32)

    def gradient_sync(self, grads: PyTree, state: PyTree,
                      n_total: Optional[int] = None, *,
                      arenas: Optional[tuple] = None,
                      membership=None):
        """Mean-all-reduce a gradient pytree over the DP axes.

        Returns (synced_grads, new_state) — or (synced_grads, new_state,
        new_arenas) when ``arenas`` is passed.  Must run inside a
        shard_map region that is manual over `inner_axis` (and
        `outer_axis` if set).

        Every ``acis*`` backend routes through one compiled switch
        program (cached per pytree structure): per leaf, a mean-reduce
        over ``axis="auto"`` — with an error-feedback target/residual
        around it on the compressed backends.  The LowerTopology pass
        turns the multi-axis reduce into the hierarchical RS/AR/AG
        schedule when an outer axis exists.

        ``membership`` switches to bounded-staleness sync: dead ranks'
        contributions are masked to the monoid identity and the mean is
        renormalized by the live count, which rides in the *same* flat
        ring buffer as the payload (``tracing.masked_reduce`` — one
        collective launch, not two).  Accepts a
        :class:`repro.elastic.Membership`, a per-rank mask array, or a
        rank-local scalar; the mask is a runtime input, so changing it
        never recompiles.  ``n_total`` is ignored on the masked path —
        the live count is the divisor.

        ``arenas`` are the persistent bucket buffers from
        :meth:`init_arenas`: the Coalesce bucket packs then write leaves
        into them in place instead of concatenating into fresh buffers.
        Thread the returned ``new_arenas`` into the next step and donate
        them at your jit boundary (``donate_argnums``) so XLA aliases
        the buffers — the pack transient drops from 2× to ~1× bucket
        size.
        """
        if self.config.backend == "xla":
            inner, outer = self.inner_axis, self.outer_axis
            axes = (inner,) if outer is None else (inner, outer)
            if membership is not None:
                # passive-network reference: two launches (payload +
                # count) — the analytic baseline the compiled one-ring
                # masked path is oracled against
                alive = self._local_alive(membership)
                count = jnp.maximum(lax.psum(alive, axes), 1.0)
                synced = jax.tree.map(
                    lambda g: lax.psum(
                        jnp.where(alive != 0, g, jnp.zeros_like(g)), axes)
                    / count.astype(g.dtype), grads)
            elif n_total is None:
                synced = jax.tree.map(
                    lambda g: lax.pmean(g, axes), grads)
            else:   # same divisor override the acis paths honor
                synced = jax.tree.map(
                    lambda g: lax.psum(g, axes) / n_total, grads)
            return (synced, state, arenas) if arenas is not None \
                else (synced, state)

        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if not leaves:                 # nothing to sync (e.g. frozen subtree)
            return (grads, state, arenas) if arenas is not None \
                else (grads, state)
        avals = tuple(jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves)
        compiled = self._sync_program(treedef, avals, n_total,
                                      masked=membership is not None)
        args = tuple(leaves)
        if self.compressed:
            args = args + tuple(treedef.flatten_up_to(state))
        if membership is not None:
            args = args + (self._local_alive(membership),)
        if arenas is not None:
            # the donation round-trip: buffers out through the step's
            # state, back in on the next sync
            _obs.RECORDER.count("arena.roundtrip")
            outs, new_arenas = compiled(*args, arenas=tuple(arenas))
        else:
            outs, new_arenas = compiled(*args), None
        synced = jax.tree_util.tree_unflatten(treedef, outs[:len(leaves)])
        new_state = state
        if self.compressed:
            new_state = jax.tree_util.tree_unflatten(
                treedef, outs[len(leaves):])
        if arenas is not None:
            return synced, new_state, new_arenas
        return synced, new_state

    def init_arenas(self, grads_like: PyTree, *,
                    axis_sizes: Optional[dict] = None,
                    n_total: Optional[int] = None,
                    masked: bool = False) -> Optional[tuple]:
        """Persistent bucket arenas for :meth:`gradient_sync` on this
        gradient pytree structure — allocated once per structure and
        cached, so repeated calls return the *same* buffers (donating
        callers get fresh ones from the sync's returned ``new_arenas``).

        Call OUTSIDE any trace (the buffers must be concrete to persist
        across steps), passing ``axis_sizes`` (``{axis: size}``) when no
        shard_map region is active — bucket boundaries depend on the DP
        ring sizes.  Returns None when the program has no bucket stages
        (xla backend, bucketing disabled, single-leaf trees).
        """
        if self.config.backend == "xla":
            return None
        leaves = jax.tree_util.tree_leaves(grads_like)
        if not leaves:
            return None
        treedef = jax.tree_util.tree_structure(grads_like)
        avals = tuple(jax.ShapeDtypeStruct(l.shape, l.dtype) for l in leaves)
        compiled = self._sync_program(treedef, avals, n_total,
                                      axis_sizes=axis_sizes, masked=masked)
        # keyed by the compiled program itself (identity): two configs
        # producing different bucket layouts for the same pytree — e.g.
        # tuned vs default bucket_bytes — must not share arenas
        hit = self._arena_cache.get(compiled)
        fresh_reason = "arena.alloc" if hit is None else None
        if hit is not None and any(
                getattr(a, "is_deleted", lambda: False)() for a in hit):
            # a donating caller consumed the cached buffers (the step
            # owns the live ones as state now) — hand out fresh arenas
            # instead of deleted arrays
            hit, fresh_reason = None, "arena.realloc"
        if hit is None:
            hit = self._arena_cache[compiled] = compiled.make_arenas()
            if hit is not None:
                _obs.RECORDER.count(fresh_reason)
        return hit

    def recompile(self, delta, grads_like: PyTree, *,
                  axis_sizes: Optional[dict] = None,
                  n_total: Optional[int] = None,
                  masked: bool = True) -> RecompileReport:
        """Re-resolve the compiled sync program and arenas after a
        topology change (a :class:`repro.elastic.TopologyDelta` or any
        object with ``shape_preserving`` / ``axis_sizes`` attributes).

        Shape-preserving deltas — rank dropout absorbed by the alive
        mask, ×k link-tier degradation — MUST hit the existing caches:
        the mask is a runtime input (not part of any compile key) and
        arenas are keyed by compiled-program identity, so both report
        100% reuse.  Only a delta that moves rank-local shapes
        (``axis_sizes`` set — e.g. a rank permanently leaving the ring)
        compiles a fresh program and allocates fresh arenas.

        The returned :class:`RecompileReport` carries the reuse/rebuild
        counters; they are also emitted to ``obs``
        (``recompile.programs_reused`` etc.) for the CI gate.
        """
        leaves = jax.tree_util.tree_leaves(grads_like)
        if not leaves or self.config.backend == "xla":
            return RecompileReport()
        treedef = jax.tree_util.tree_structure(grads_like)
        avals = tuple(jax.ShapeDtypeStruct(l.shape, l.dtype)
                      for l in leaves)
        sizes = dict(axis_sizes or {})
        shape_preserving = bool(getattr(delta, "shape_preserving", True))
        if not shape_preserving:
            sizes.update(dict(getattr(delta, "axis_sizes", None) or {}))
        with _obs.recording() as rec:
            compiled = self._sync_program(
                treedef, avals, n_total, axis_sizes=sizes or None,
                masked=masked)
            arenas = self.init_arenas(
                grads_like, axis_sizes=sizes or None, n_total=n_total,
                masked=masked)
        prog_rebuilt = int(rec.counter("compile.cache_miss") > 0)
        arena_rebuilt = 0 if arenas is None else int(
            rec.counter("arena.alloc") + rec.counter("arena.realloc") > 0)
        report = RecompileReport(
            programs_reused=1 - prog_rebuilt,
            programs_rebuilt=prog_rebuilt,
            arenas_reused=0 if arenas is None else 1 - arena_rebuilt,
            arenas_rebuilt=arena_rebuilt,
            shape_preserving=shape_preserving)
        _obs.RECORDER.count("recompile.programs_reused",
                            report.programs_reused)
        _obs.RECORDER.count("recompile.programs_rebuilt",
                            report.programs_rebuilt)
        _obs.RECORDER.count("recompile.arenas_reused",
                            report.arenas_reused)
        _obs.RECORDER.count("recompile.arenas_rebuilt",
                            report.arenas_rebuilt)
        _obs.RECORDER.event("engine.recompile",
                            shape_preserving=shape_preserving,
                            full=report.full_recompile)
        self._last_sync = compiled
        return report

    def _sync_program(self, treedef, avals: tuple,
                      n_total: Optional[int] = None, *,
                      axis_sizes: Optional[dict] = None,
                      masked: bool = False):
        """Build (or fetch) the compiled gradient-sync switch program for
        one pytree structure.

        ``avals`` (one per leaf) give SelectSchedule per-leaf payload
        sizes; axis sizes are read live via ``lax.axis_size`` — we are
        inside the caller's shard_map region at trace time — unless
        ``axis_sizes`` supplies them explicitly (the outside-trace
        spelling :meth:`init_arenas` uses), so the per-tier ring
        crossover is reachable without a mesh in hand.
        """
        cfg = self.config
        inner, outer = self.inner_axis, self.outer_axis
        compressed = self.compressed
        n_leaves = len(avals)
        sizes = live_axis_sizes((inner, outer), axis_sizes)
        # the sizes are part of the key: the same engine may serve meshes
        # of different DP size, and the schedule choice depends on them.
        # The config's cache_key is too — the autotuner hands back
        # configs differing only in tuned fields, and those must compile
        # to distinct programs, not collide with the default's entry.
        key0 = (treedef, avals, n_total, tuple(sorted(sizes.items())),
                masked)
        cfg_eff = cfg
        if cfg.autotune and sizes.get(inner):
            cfg_eff = self._tune_cache.get(key0)
            if cfg_eff is None:
                cfg_eff = self._tuned_sync_config(
                    avals, n_total, sizes)
                self._tune_cache[key0] = cfg_eff
        key = key0 + (cfg_eff.cache_key(),)
        hit = self._sync_cache.get(key)
        if hit is not None:
            _obs.RECORDER.count("compile.cache_hit")
            self._last_sync = hit
            return hit
        _obs.RECORDER.count("compile.cache_miss")
        compiled = self._build_sync(cfg_eff, avals, n_total, sizes,
                                    masked=masked)
        self._sync_cache[key] = compiled
        self._last_sync = compiled
        return compiled

    def _tuned_sync_config(self, avals, n_total, sizes):
        """Resolve the effective config through the tuning DB: a stored
        winner for this (pytree structure, topology) applies directly; a
        miss searches the tunable space offline (analytic replay over
        recompiled candidates) and persists the winner."""
        from repro import tune

        cfg = self.config
        topo = self.topology(axis_size=sizes)
        in_avals = avals + (avals if self.compressed else ())
        tkey = tune.plan_key(
            f"gradient_sync[{cfg.backend}x{len(avals)}]",
            in_avals, topo, cfg)
        return tune.tuned_config(
            cfg,
            lambda c: self._build_sync(c, avals, n_total, sizes),
            key=tkey, db_path=cfg.tune_db)

    def _build_sync(self, cfg, avals, n_total, sizes, *,
                    masked: bool = False):
        """Trace + compile the gradient-sync program under ``cfg`` (also
        the candidate builder the autotune search recompiles with).

        ``masked=True`` builds the bounded-staleness variant: one extra
        scalar input (this rank's alive flag), per-leaf
        ``masked_reduce`` with renormalization — the live count travels
        in the payload's flat bucket, so the program has the same ring
        structure (and the same stage count) as the unmasked one.  On
        the compressed backends the masked target feeds the usual EF
        triple and one tiny exact scalar reduce carries the live count.
        """
        inner, outer = self.inner_axis, self.outer_axis
        compressed = self.compressed
        n_leaves = len(avals)

        def _mean(y):
            n = n_total
            if n is None:
                n = lax.axis_size(inner)
                if outer is not None:
                    n = n * lax.axis_size(outer)
            return y / n

        def _ef_target(g, r):
            return g + r.astype(g.dtype)

        def _masked_ef_target(g, r, a):
            t = g + r.astype(g.dtype)
            return jnp.where(a != 0, t, jnp.zeros_like(t))

        def _ef_residual(t, delivered, r):
            return (t.astype(jnp.float32) - delivered).astype(r.dtype)

        def _masked_mean(y, c):
            return y / jnp.maximum(c, 1).astype(y.dtype)

        def sync(*args):
            if masked:
                alive = args[-1]
                args = args[:-1]
            gs, rs = args[:n_leaves], args[n_leaves:]
            outs, news = [], []
            cnt = None
            if masked and compressed:
                # the EF wire is lossy; the divisor must not be — one
                # exact scalar ring carries the live count for all leaves
                cnt = tracing.reduce(alive, ADD, axis="auto")
            for i in range(n_leaves):
                if compressed:
                    if masked:
                        t = tracing.map(_masked_ef_target, gs[i], rs[i],
                                        alive, name="masked_ef_target")
                    else:
                        t = tracing.map(_ef_target, gs[i], rs[i],
                                        name="ef_target")
                    red, dlv = tracing.ef_reduce(
                        t, compressor=cfg.compressor,
                        topk_ratio=cfg.topk_ratio, axis="auto")
                    if masked:
                        outs.append(tracing.map(_masked_mean, red, cnt,
                                                name="masked_mean"))
                    else:
                        outs.append(tracing.map(_mean, red, name="mean",
                                                elementwise=True))
                    news.append(tracing.map(_ef_residual, t, dlv, rs[i],
                                            name="ef_residual"))
                elif masked:
                    red, _ = tracing.masked_reduce(gs[i], alive, ADD,
                                                   axis="auto")
                    outs.append(red)
                else:
                    red = tracing.reduce(gs[i], ADD, axis="auto")
                    outs.append(tracing.map(_mean, red, name="mean",
                                            elementwise=True))
            return tuple(outs) + tuple(news)

        tag = "masked," if masked else ""
        prog = tracing.trace(
            sync, name=f"gradient_sync[{tag}{cfg.backend}x{n_leaves}]",
            num_inputs=n_leaves * (2 if compressed else 1) + int(masked))
        in_avals = avals + (avals if compressed else ()) \
            + ((jax.ShapeDtypeStruct((), jnp.float32),) if masked else ())
        return compiler.compile_rank_local(
            prog, inner, axis_size=sizes.get(inner), config=cfg,
            in_avals=in_avals, topology=self.topology(axis_size=sizes))

    def last_sync_program(self):
        """The most recently compiled (or cache-hit) gradient-sync
        :class:`~repro.core.compiler.CompiledProgram`, or None before the
        first sync — the stable way for drivers to print ``explain()`` /
        ``program_time()`` for the program that actually ran."""
        return self._last_sync

    # -- generic ops (used by MoE dispatch, GCN, examples) -------------------

    def all_reduce(self, x, axis_name=None, monoid=ADD):
        return collectives.all_reduce(
            x, axis_name or self.inner_axis, monoid,
            backend=self.base_backend)

    def all_gather(self, x, axis_name=None):
        return collectives.all_gather(
            x, axis_name or self.inner_axis, backend=self.base_backend)

    def reduce_scatter(self, x, axis_name=None, monoid=ADD):
        return collectives.reduce_scatter(
            x, axis_name or self.inner_axis, monoid,
            backend=self.base_backend)

    def all_to_all(self, x, axis_name=None):
        return collectives.all_to_all(
            x, axis_name or self.inner_axis, backend=self.base_backend)

    # -- switch-program compilation (the one entry point) --------------------

    def compile(self, prog, mesh=None, in_specs=None, out_specs=None, *,
                axis_name: Optional[str] = None, in_avals=None,
                axis_size=None, jit: bool = True):
        """Compile a switch program through the pass pipeline.

        ``prog`` may be a plain Python function over traced values (see
        :mod:`repro.core.tracing`), a traced :class:`DagProgram`, or a
        legacy chain :class:`SwitchProgram`.  With ``mesh`` (plus
        in/out specs) the result is the jitted shard_map "CGRA binary";
        without it, a rank-local :class:`CompiledProgram` for use inside an
        existing shard_map region.  The engine's
        :class:`CollectiveConfig` drives the SelectSchedule pass
        (``latency_optimal_below`` ring crossover); pass ``in_avals``
        (rank-local ShapeDtypeStructs or arrays, one per program input) to
        give the scheduler payload sizes.  The engine's DP axes form the
        compile :class:`~repro.core.compiler.Topology`, so ops written
        with ``axis="auto"`` lower hierarchically across inner and outer.
        """
        ax = axis_name or self.inner_axis
        topo = self.topology(mesh, axis_size=axis_size)
        if isinstance(axis_size, dict):
            axis_size = axis_size.get(ax)
        cfg = self.config
        if cfg.autotune and in_avals is not None:
            # candidates are scored on rank-local plans (cheap analytic
            # replay); the winning config then drives the real compile,
            # mesh-wrapped or not
            from repro import tune
            from repro.core import program as _program
            from repro.core import tracing

            name = getattr(prog, "name", None) \
                or getattr(prog, "__name__", "program")
            if not isinstance(prog, (_program.DagProgram,
                                     _program.SwitchProgram)):
                # trace once, not once per search candidate — and in_avals
                # fixes the arity for *args-signature programs, which
                # trace() alone cannot infer
                prog = tracing.trace(prog, num_inputs=len(in_avals))
            cfg = tune.tuned_config(
                cfg,
                lambda c: compiler.compile_rank_local(
                    prog, ax, axis_size=axis_size, config=c,
                    in_avals=in_avals, topology=topo),
                key=tune.plan_key(name, in_avals, topo, cfg),
                db_path=cfg.tune_db)
        if mesh is None:
            return compiler.compile_rank_local(
                prog, ax, axis_size=axis_size, config=cfg,
                in_avals=in_avals, topology=topo)
        if in_specs is None or out_specs is None:
            raise ValueError("mesh compilation needs in_specs and out_specs")
        return compiler.compile_program(
            prog, mesh, ax, in_specs, out_specs, jit=jit,
            config=cfg, in_avals=in_avals, topology=topo)


def make_engine(backend: str = "xla", *, inner_axis: str = "data",
                outer_axis: Optional[str] = None, **kw) -> CollectiveEngine:
    return CollectiveEngine(CollectiveConfig(backend=backend, **kw),
                            inner_axis=inner_axis, outer_axis=outer_axis)
