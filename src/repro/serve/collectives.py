"""Compiled serving data path — decode/prefill collectives as switch programs.

Tensor-parallel serving splits every layer's matmuls across a ``tp`` mesh
axis, which turns the decode hot loop into a *communication* loop: one
all-reduce of attention partials and one of FFN partials per layer, plus
the MoE group->expert all-to-all dispatch/combine.  This module expresses
those as traced :mod:`repro.core` programs compiled through
``engine.compile`` — the same Legalize → … → Emit pipeline (and the same
bucketing / batched-ring / Pallas-kernel / autotune machinery) the
training sync path uses — and installs them into the models via the
:class:`repro.models.parallel.TensorParallel` hook.

Three hook transports, selected by ``mode``:

  * ``xla``      — ``lax.psum`` / XLA all_to_all (passive-network baseline)
  * ``direct``   — per-op acis ring collectives, no compiler (the
                   "uncompiled" acis path the benchmark beats)
  * ``compiled`` — switch programs from :meth:`ServeCollectives.program`:
                   sub-crossover decode payloads get the log-step
                   latency-optimal schedule, the MoE combine all-to-all
                   fuses with the shared-expert all-reduce into one
                   Type-4 ``allreduce+alltoall`` stage (FuseHops), and
                   ``use_kernels`` / ``batch_rings`` / ``autotune`` apply
                   exactly as in training.

Programs are cached in a process-wide :class:`SwitchProgramCache` shared
by every engine replica — N replicas serving the same model compile each
decode-shape program once (``serve.program_cache_hit/miss`` counters).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import collectives as C
from repro.core import tracing
from repro.core.api import CollectiveConfig, CollectiveEngine
from repro.core.types import ADD
from repro.models import moe as MOE
from repro.models import parallel as TP
from repro.models.config import ModelConfig
from repro.models.transformer import layer_schedule
from repro.obs import metrics as _obs
from repro.tune.search import plan_key

PyTree = Any


# ---------------------------------------------------------------------------
# the shared program cache
# ---------------------------------------------------------------------------

class SwitchProgramCache:
    """Process-wide compiled-program store shared across serving replicas.

    Keyed by a :func:`repro.tune.search.plan_key`-style hash of (program
    name, rank-local input avals, topology) plus the config's
    ``cache_key()`` — the same identity the tuning DB uses, so two
    replicas of the same model at the same batch shape share every
    program, while a replica running a tuned or kernel-enabled config
    compiles its own.  Hits and misses land on the process recorder
    (``serve.program_cache_hit`` / ``serve.program_cache_miss``).
    """

    def __init__(self):
        self._programs: dict = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get_or_build(self, key, build: Callable[[], Any]):
        with self._lock:
            hit = self._programs.get(key)
            if hit is not None:
                self.hits += 1
                _obs.RECORDER.count("serve.program_cache_hit")
                return hit
        # compile outside the lock (compiles can nest cache lookups via
        # autotune); last writer wins on a racing double-compile
        _obs.RECORDER.count("serve.program_cache_miss")
        prog = build()
        with self._lock:
            self._programs[key] = prog
            self.misses += 1
        return prog

    def __len__(self) -> int:
        return len(self._programs)

    def stats(self) -> dict:
        return {"programs": len(self._programs),
                "hits": self.hits, "misses": self.misses}

    def clear(self):
        with self._lock:
            self._programs.clear()
            self.hits = self.misses = 0


#: Default cache — every :class:`ServeCollectives` that is not handed an
#: explicit cache shares this one, so replicas co-located in a process
#: compile each program once.
PROGRAM_CACHE = SwitchProgramCache()


# ---------------------------------------------------------------------------
# hook transports
# ---------------------------------------------------------------------------

class _TPBase(TP.TensorParallel):
    """Shared dispatch/combine plumbing; subclasses supply the transport.

    MoE resharding with replicated tokens (serving keeps activations
    replicated across tp; only weights are sliced):

      dispatch: all ranks hold the identical slot tensor [E, S, D]; the
        all-to-all hands rank r the rows of *its* E/tp experts — chunk r
        of every peer's (identical) input — so we keep block 0 of the
        [tp, E/tp, ...] output.
      combine: rank r tiles its local expert outputs [E/tp, S, D] tp
        times so every destination receives them; the all-to-all output
        is then the full [E, S, D] in expert order on every rank.

    Both are pure data movement — bit-exact against the unhooked path.
    """

    def __init__(self, axis: str, tp: int):
        self.axis = axis
        self.tp = tp

    # transport primitives -------------------------------------------------
    def _all_reduce(self, x):
        raise NotImplementedError

    def _all_to_all(self, x):
        raise NotImplementedError

    def _fused_combine(self, shared, tiled):
        """(all_reduce(shared), all_to_all(tiled)) — overridden where the
        pair can fuse into one switch stage."""
        return self._all_reduce(shared), self._all_to_all(tiled)

    # the model-facing hook ------------------------------------------------
    def attn_reduce(self, h):
        return self._all_reduce(h)

    def ffn_reduce(self, f):
        return self._all_reduce(f)

    def moe_dispatch(self, xem):
        e = xem.shape[0]
        el = e // self.tp
        out = self._all_to_all(xem)
        return out.reshape((self.tp, el) + xem.shape[1:])[0]

    def moe_combine(self, yem, shared_partial=None):
        tiled = jnp.broadcast_to(
            yem[None], (self.tp,) + yem.shape).reshape(
                (self.tp * yem.shape[0],) + yem.shape[1:])
        if shared_partial is None:
            return self._all_to_all(tiled), None
        reduced, full = self._fused_combine(shared_partial, tiled)
        return full, reduced


class XlaTPHook(_TPBase):
    """Passive-network baseline: XLA built-ins."""

    def _all_reduce(self, x):
        return lax.psum(x, self.axis)

    def _all_to_all(self, x):
        return C.all_to_all(x, self.axis, backend="xla")


class DirectTPHook(_TPBase):
    """Per-op acis ring collectives — the uncompiled acis path.  Every
    call is its own bandwidth-optimal ring (2(n-1) hops); nothing is
    scheduled, fused, or batched.  The A/B baseline ``benchmarks/serve.py``
    measures the compiler against."""

    def _all_reduce(self, x):
        return C.all_reduce(x, self.axis, ADD, backend="acis")

    def _all_to_all(self, x):
        return C.all_to_all(x, self.axis, backend="acis")


class CompiledTPHook(_TPBase):
    """Switch programs from the shared cache, built on first use per
    rank-local aval (decode and prefill shapes get distinct programs)."""

    def __init__(self, sc: "ServeCollectives"):
        super().__init__(sc.axis, sc.tp)
        self.sc = sc

    @staticmethod
    def _aval(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    def _all_reduce(self, x):
        prog = self.sc.program("serve_tp_allreduce",
                               self.sc._trace_allreduce, (self._aval(x),))
        return prog(x)[0]

    def _all_to_all(self, x):
        prog = self.sc.program("serve_moe_alltoall",
                               self.sc._trace_alltoall, (self._aval(x),))
        return prog(x)[0]

    def _fused_combine(self, shared, tiled):
        prog = self.sc.program(
            "serve_moe_combine", self.sc._trace_combine,
            (self._aval(shared), self._aval(tiled)))
        return prog(shared, tiled)


_MODES = ("compiled", "direct", "xla")


# ---------------------------------------------------------------------------
# ServeCollectives — sharding rules + program factory for one model config
# ---------------------------------------------------------------------------

class ServeCollectives:
    """Tensor-parallel serving plan for one :class:`ModelConfig`.

    Owns the ``tp`` mesh, the per-leaf parameter/cache
    :class:`PartitionSpec` rules, the rank-local decode wrapper
    (:meth:`decode_fn` — a drop-in for ``ServeEngine``'s jitted decode),
    and the switch-program factory backed by a shared
    :class:`SwitchProgramCache`.

    Supported families: ``dense`` and ``moe`` (GQA attention; MLA caches
    are 57× smaller and latent-projected — slicing them is a different
    PR).  ``tp`` must divide ``n_heads``, ``n_kv_heads``, every FFN
    hidden dim, and (moe) the experts each layer holds.
    """

    def __init__(self, cfg: ModelConfig, tp: int, *, axis: str = "tp",
                 config: Optional[CollectiveConfig] = None,
                 cache: Optional[SwitchProgramCache] = None,
                 devices=None):
        if cfg.family not in ("dense", "moe"):
            raise NotImplementedError(
                f"tensor-parallel serving supports dense/moe stacks, "
                f"not family={cfg.family!r}")
        if cfg.family == "moe" and cfg.mla is not None:
            raise NotImplementedError("MLA cache slicing not supported")

        def div(what, n):
            if n % tp:
                raise ValueError(f"tp={tp} must divide {what}={n}")
        div("n_heads", cfg.n_heads)
        div("n_kv_heads", cfg.n_kv_heads)
        div("d_ff", cfg.d_ff)
        if cfg.family == "moe":
            div("held experts", cfg.moe.held_range[1])
            div("moe.d_ff_dense", cfg.moe.d_ff_dense or cfg.d_ff)
            if cfg.moe.n_shared:
                div("moe.d_ff_shared", cfg.moe.d_ff_shared
                    or cfg.moe.n_shared * cfg.moe.d_ff_expert)

        self.cfg = cfg
        self.tp = tp
        self.axis = axis
        self.config = config if config is not None \
            else CollectiveConfig(backend="acis")
        if self.config.backend == "xla":
            raise ValueError("compiled serving needs an acis backend; "
                             "use mode='xla' for the XLA baseline")
        self.cache = cache if cache is not None else PROGRAM_CACHE
        self.engine = CollectiveEngine(self.config, inner_axis=axis)
        if devices is None:
            devices = jax.devices()[:tp]
        if len(devices) != tp:
            raise ValueError(f"need {tp} devices, got {len(devices)}")
        self.mesh = jax.sharding.Mesh(devices, (axis,))
        # rank-local view: each rank runs the same decode math over its
        # head/expert slice; head counts shrink, everything else (incl.
        # moe.held_range — routing is replicated, expert compute reads
        # the sliced param shapes) stays the model's.
        self.cfg_local = dataclasses.replace(
            cfg, n_heads=cfg.n_heads // tp, n_kv_heads=cfg.n_kv_heads // tp,
            d_head=cfg.head_dim)   # pin: head_dim derives from n_heads

    # -- traced program bodies (named methods so benchmarks can reuse) ------

    def _trace_allreduce(self, v):
        return tracing.reduce(v, ADD, axis=self.axis)

    def _trace_alltoall(self, v):
        return tracing.all_to_all(v, axis=self.axis)

    def _trace_combine(self, s, t):
        # independent same-axis REDUCE + ALLTOALL: FuseHops merges them
        # into one Type-4 allreduce+alltoall stage
        return (tracing.reduce(s, ADD, axis=self.axis),
                tracing.all_to_all(t, axis=self.axis))

    # -- program factory ----------------------------------------------------

    def program(self, name: str, fn, avals: tuple):
        """Compiled switch program for ``fn`` at ``avals``, from the
        shared cache.  The key is the tune-DB :func:`plan_key` identity
        plus the full config ``cache_key()`` (tuned/kernel variants must
        not collide)."""
        topo = self.engine.topology(axis_size={self.axis: self.tp})
        key = (plan_key(name, avals, topo, self.config),
               self.config.cache_key())
        return self.cache.get_or_build(
            key, lambda: self.engine.compile(
                fn, in_avals=avals, axis_size={self.axis: self.tp}))

    def hook(self, mode: str = "compiled") -> _TPBase:
        if mode == "compiled":
            return CompiledTPHook(self)
        if mode == "direct":
            return DirectTPHook(self.axis, self.tp)
        if mode == "xla":
            return XlaTPHook(self.axis, self.tp)
        raise ValueError(f"mode {mode!r} not in {_MODES}")

    # -- per-leaf sharding rules -------------------------------------------

    def _param_spec(self, path, leaf) -> P:
        keys = [k.key for k in path
                if isinstance(k, jax.tree_util.DictKey)]
        name = keys[-1] if keys else ""
        nd = leaf.ndim
        ax = self.axis
        if "experts" in keys:
            # stacked expert weights [..., E, d_in, d_out]: slice E
            return P(*(None,) * (nd - 3), ax, None, None)
        if name in ("wq", "wk", "wv", "wi", "wi_gate", "wi_up"):
            return P(*(None,) * (nd - 1), ax)      # column (head/ff) slice
        if name == "wo":
            return P(*(None,) * (nd - 2), ax, None)  # row slice -> partials
        return P()      # norms, router, embed, lm_head, gates: replicated

    def _cache_spec(self, path, leaf) -> P:
        keys = [k.key for k in path
                if isinstance(k, jax.tree_util.DictKey)]
        name = keys[-1] if keys else ""
        if name in ("k", "v"):
            # [..., B, S, Hkv, dh]: slice the kv-head dim
            return P(*(None,) * (leaf.ndim - 2), self.axis, None)
        raise ValueError(f"unsupported cache leaf {'/'.join(keys)}")

    def param_specs(self, params: PyTree) -> PyTree:
        return jax.tree_util.tree_map_with_path(self._param_spec, params)

    def cache_specs(self, cache: PyTree) -> PyTree:
        return jax.tree_util.tree_map_with_path(self._cache_spec, cache)

    def shardings(self, specs: PyTree) -> PyTree:
        """``NamedSharding`` on the ``tp`` mesh for every spec of a
        :meth:`param_specs` / :meth:`cache_specs` tree — e.g. the
        ``out_shardings`` that create parameters already sharded:
        ``jax.jit(model.init, out_shardings=sc.shardings(
        sc.param_specs(model.param_shapes())))``."""
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                            is_leaf=lambda s: isinstance(s, P))

    # -- the decode program -------------------------------------------------

    def decode_fn(self, params: PyTree, cache: PyTree, *,
                  mode: str = "compiled", donate: bool = True):
        """Jitted ``(params, token, cache, index) -> (logits, cache)``
        with the same contract as ``ServeEngine``'s plain decode, full
        logits out.  Parameters and cache are pinned to their TP
        shardings (``in_shardings``/``out_shardings``): trees placed that
        way — as ``ServeEngine`` places them — never move, and the
        returned cache keeps the layout, so it stays sharded,
        device-resident and donated across ticks.  Trees placed
        otherwise are resharded at every call.

        ``params``/``cache`` are exemplars for spec-tree construction
        only; any same-structure trees may be passed at call time.
        """
        from repro.models import decode as D

        hook = self.hook(mode)
        if mode == "compiled":
            # build the tick's programs eagerly (outside any trace): the
            # hook's trace-time lookups then hit the shared cache
            self.decode_programs(self._batch_of(cache))
        cfg_local = self.cfg_local
        pspecs = self.param_specs(params)
        cspecs = self.cache_specs(cache)

        def decode_tick(p, tok, c, idx):
            with TP.tensor_parallel(hook):
                return D.decode_step(p, cfg_local, tok, c, idx)

        fn = jax.shard_map(decode_tick, mesh=self.mesh,
                           in_specs=(pspecs, P(), cspecs, P()),
                           out_specs=(P(), cspecs), check_vma=False)
        rep = NamedSharding(self.mesh, P())
        cache_sh = self.shardings(cspecs)
        return jax.jit(fn,
                       in_shardings=(self.shardings(pspecs), rep, cache_sh,
                                     rep),
                       out_shardings=(rep, cache_sh),
                       donate_argnums=(2,) if donate else ())

    @staticmethod
    def _batch_of(cache: PyTree) -> int:
        leaf = jax.tree.leaves(cache)[0]
        # stacked layer caches are [P, B, S, H, dh]; unstacked [B, S, H, dh]
        return leaf.shape[1] if leaf.ndim >= 5 else leaf.shape[0]

    # -- analytic costs (SLO admission, benchmarks) -------------------------

    def decode_programs(self, batch: int) -> list[tuple[str, Any, int]]:
        """The switch programs one decode tick runs, as
        ``(name, CompiledProgram, calls-per-tick)`` — built (or fetched)
        from the shared cache with the exact avals the hook will use."""
        return self._tick_programs(batch, 1)

    def prefill_programs(self, batch: int, t: int):
        """Programs of one *batched* prefill pass over a [batch, t]
        prompt (the ``model.prefill`` formulation — ``ServeEngine``'s
        in-batch prefill instead pays ``t`` decode ticks)."""
        return self._tick_programs(batch, t)

    def _tick_programs(self, b: int, t: int):
        cfg = self.cfg
        dt = jnp.bfloat16
        d = cfg.d_model
        sds = jax.ShapeDtypeStruct
        counts: dict[str, list] = {}

        def add(name, fn, avals):
            prog = self.program(name, fn, avals)
            ent = counts.setdefault(name, [prog, 0])
            ent[1] += 1

        n_tok = b * t
        g = MOE._n_groups(n_tok)
        ng = n_tok // g
        for kind in layer_schedule(cfg):
            add("serve_tp_allreduce", self._trace_allreduce,
                (sds((b, t, d), dt),))               # attention partials
            if kind != "moe_self":
                add("serve_tp_allreduce", self._trace_allreduce,
                    (sds((b, t, d), dt),))           # dense-FFN partials
                continue
            m = cfg.moe
            cap = ng if t == 1 else max(
                1, int(ng * m.top_k * m.capacity_factor / m.n_experts))
            slot = (m.held_range[1], g * cap, d)
            add("serve_moe_alltoall", self._trace_alltoall, (sds(slot, dt),))
            if m.n_shared:
                add("serve_moe_combine", self._trace_combine,
                    (sds((g, ng, d), dt), sds(slot, dt)))
            else:
                add("serve_moe_alltoall", self._trace_alltoall,
                    (sds(slot, dt),))
        return [(name, prog, n) for name, (prog, n) in counts.items()]

    def decode_comm_time(self, batch: int) -> float:
        """Analytic switch time (seconds) of one decode tick's
        communication — ``program_time`` over the tick's programs."""
        return sum(prog.program_time() * n
                   for _, prog, n in self.decode_programs(batch))

    def prefill_comm_time(self, batch: int, t: int) -> float:
        """Analytic switch time (seconds) of one batched prefill pass."""
        return sum(prog.program_time() * n
                   for _, prog, n in self.prefill_programs(batch, t))
