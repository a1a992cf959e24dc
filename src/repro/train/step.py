"""Train-step builders.

Two execution strategies, selected by the collective backend:

  * ``gspmd`` (backend "xla") — everything under pjit/GSPMD: params
    FSDP×TP sharded, gradient reduction and TP collectives inserted by the
    partitioner.  The passive-network baseline; also the path every dry-run
    cell lowers through.

  * ``acis`` (backends "acis*") — the gradient-sync phase runs in a
    `shard_map` region that is *manual* over the DP axes and auto over
    "model": per-shard grads are synchronized explicitly through the
    CollectiveEngine (ring / hierarchical / compressed-with-error-feedback),
    then the optimizer applies the update inside the region.  This is the
    paper's MPI-transparency point: the model code is identical, only the
    transport changed.  Params are replicated over DP axes in this mode
    (TP/EP sharding over "model" still applies).

Both support microbatched gradient accumulation (lax.scan) — the
communication-efficiency knob that interacts with compression (one sync per
step regardless of microbatch count).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: F401

from repro.core.api import CollectiveEngine
from repro.models.model import Model
from repro.obs import metrics as _obs
from repro.sharding import rules
from repro.train.loss import cross_entropy
from repro.train.optimizer import Optimizer

PyTree = Any


@dataclasses.dataclass
class TrainState:
    params: PyTree
    opt: PyTree
    step: jax.Array
    ef_residual: Optional[PyTree] = None   # Type 3 look-aside memory
    # persistent gradient-sync bucket arenas (engine.init_arenas):
    # threaded through the step and donated with the state, so the
    # Coalesce bucket packs write in place instead of re-allocating a 2×
    # transient every sync
    sync_arenas: Optional[tuple] = None


jax.tree_util.register_pytree_node(
    TrainState,
    lambda s: ((s.params, s.opt, s.step, s.ef_residual, s.sync_arenas),
               None),
    lambda aux, ch: TrainState(*ch))


def _loss_fn(model: Model, params, tokens, context, mesh: Optional[Mesh]):
    """tokens: [b, T+1] — inputs tokens[:, :-1], targets tokens[:, 1:]."""
    hidden, aux = model.forward(params, tokens[:, :-1], context=context)
    logits = model.logits(params, hidden)
    if mesh is not None:
        logits = rules.constrain(logits, mesh, rules.logits_spec(mesh))
    loss, metrics = cross_entropy(logits, tokens[:, 1:])
    metrics["aux"] = aux
    return loss + aux, metrics


def _accumulate_grads(model, params, batch, microbatches, mesh):
    """lax.scan over microbatch slices; returns (mean grads, mean metrics)."""
    tokens = batch["tokens"]
    context = batch.get("context")
    b = tokens.shape[0]
    assert b % microbatches == 0, (b, microbatches)
    mb = b // microbatches

    def grads_of(tok, ctx):
        return jax.grad(
            lambda p: _loss_fn(model, p, tok, ctx, mesh), has_aux=True
        )(params)

    if microbatches == 1:
        g, m = grads_of(tokens, context)
        return g, m

    tok_mb = tokens.reshape(microbatches, mb, *tokens.shape[1:])
    ctx_mb = None if context is None else \
        context.reshape(microbatches, mb, *context.shape[1:])
    if mesh is not None:
        # keep the BATCH dim data-sharded after the microbatch split —
        # otherwise GSPMD happily shards the microbatch dim over 'data'
        # and inserts full-rematerialization resharding inside the scan.
        dp = rules.dp_axes(mesh, model.cfg.parallelism)
        tok_mb = rules.constrain(
            tok_mb, mesh, P(None, dp, *([None] * (tok_mb.ndim - 2))))
        if ctx_mb is not None:
            ctx_mb = rules.constrain(
                ctx_mb, mesh, P(None, dp, *([None] * (ctx_mb.ndim - 2))))

    def body(acc, xs):
        tok, ctx = xs
        g, m = grads_of(tok, ctx)
        acc_g, acc_m = acc
        acc_g = jax.tree.map(lambda a, x: a + x.astype(a.dtype), acc_g, g)
        acc_m = jax.tree.map(lambda a, x: a + x, acc_m, m)
        return (acc_g, acc_m), ()

    g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    m0 = {"nll": 0.0, "z_loss": 0.0, "accuracy": 0.0, "aux": 0.0}
    m0 = jax.tree.map(jnp.float32, m0)
    xs = (tok_mb, ctx_mb) if ctx_mb is not None else (tok_mb, None)
    unroll = microbatches if model.cfg.analysis_unroll else 1
    if ctx_mb is None:
        (g, m), _ = jax.lax.scan(
            lambda acc, tok: body(acc, (tok, None)), (g0, m0), tok_mb,
            unroll=unroll)
    else:
        (g, m), _ = jax.lax.scan(body, (g0, m0), xs, unroll=unroll)
    inv = 1.0 / microbatches
    return jax.tree.map(lambda x: x * inv, g), \
        jax.tree.map(lambda x: x * inv, m)


# ---------------------------------------------------------------------------
# GSPMD strategy (xla backend / dry-run path)
# ---------------------------------------------------------------------------

def build_train_step_gspmd(model: Model, optimizer: Optimizer, mesh: Mesh,
                           *, microbatches: int = 1,
                           donate: bool = True) -> Callable:
    """Returns jitted (state, batch) -> (state, metrics) with sharded I/O."""

    def step_fn(state: TrainState, batch) -> tuple[TrainState, dict]:
        from repro.sharding.act import activation_sharding
        with activation_sharding(mesh, parallelism=model.cfg.parallelism):
            return _step_body(state, batch)

    def _step_body(state: TrainState, batch) -> tuple[TrainState, dict]:
        grads, metrics = _accumulate_grads(
            model, state.params, batch, microbatches, mesh)
        new_params, new_opt = optimizer.update(
            grads, state.opt, state.params, state.step)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree.leaves(grads)))
        metrics["grad_norm"] = gn
        return TrainState(new_params, new_opt, state.step + 1,
                          state.ef_residual), metrics

    par = model.cfg.parallelism
    pspecs = rules.param_specs(model.param_shapes(), mesh, par)
    opt_shapes = jax.eval_shape(optimizer.init, model.param_shapes())
    ospecs = _opt_specs(opt_shapes, pspecs)
    state_specs = TrainState(pspecs, ospecs, P(), None)
    batch_specs = {"tokens": rules.batch_spec(mesh, extra_dims=1,
                                              parallelism=par)}
    if model.context_inputs(1) is not None:   # stub-modality archs
        batch_specs["context"] = rules.batch_spec(mesh, extra_dims=2,
                                                  parallelism=par)
    out_metric_specs = {k: P() for k in
                        ("nll", "z_loss", "accuracy", "aux", "grad_norm")}
    state_shardings = _ns(mesh, state_specs)
    fn = jax.jit(
        step_fn,
        in_shardings=(state_shardings, _ns(mesh, batch_specs)),
        out_shardings=(state_shardings, _ns(mesh, out_metric_specs)),
        donate_argnums=(0,) if donate else (),
    )
    fn.state_shardings = state_shardings  # type: ignore[attr-defined]
    fn.place_state = lambda st: jax.device_put(st, state_shardings)  # type: ignore[attr-defined]
    return fn


def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if isinstance(s, P) else s,
        spec_tree, is_leaf=lambda s: isinstance(s, P) or s is None)


def _opt_specs(opt_shapes: PyTree, pspecs: PyTree) -> PyTree:
    """Optimizer-state sharding: match the param's spec when the shapes
    coincide (m/v), drop trailing axes for factored stats, scalars repl."""
    flat_p = {tuple(str(k) for k in path): spec
              for path, spec in
              jax.tree_util.tree_flatten_with_path(pspecs)[0]}

    def one(path, leaf):
        # find a param spec whose path is a suffix-compatible prefix
        keys = tuple(str(k) for k in path)
        for pk, spec in flat_p.items():
            if all(any(pp == kk for kk in keys) for pp in pk):
                if len(spec) == len(leaf.shape):
                    return spec
                # factored stats: take leading dims of the param spec
                return P(*tuple(spec)[:len(leaf.shape)])
        return P()

    return jax.tree_util.tree_map_with_path(one, opt_shapes)


# ---------------------------------------------------------------------------
# ACiS strategy (explicit in-network gradient sync)
# ---------------------------------------------------------------------------

def build_train_step_acis(model: Model, optimizer: Optimizer, mesh: Mesh,
                          engine: CollectiveEngine, *,
                          microbatches: int = 1,
                          donate: bool = False,
                          recorder=None) -> Callable:
    """Params replicated over DP axes (TP over 'model' untouched); gradient
    sync + update run manual-over-DP via the CollectiveEngine.

    When the state carries ``sync_arenas`` (see :func:`init_state` with
    ``arenas=True``), they are threaded through the sync and returned in
    the new state; pass ``donate=True`` so the whole state — arenas
    included — is donated to the step and XLA writes the bucket packs in
    place instead of allocating a 2× transient per sync.  ``donate``
    invalidates the state passed in (the usual donation contract), so it
    is opt-in.

    ``recorder`` (a :class:`repro.obs.Recorder`) wraps the jitted step
    with host-side telemetry: ``train.steps`` counts calls, and — only
    when the recorder is enabled — ``train.step_s`` observes blocking
    wall-clock per step (the block changes dispatch overlap, so it is
    never imposed on un-recorded runs).  Defaults to the process-wide
    ``obs`` recorder read at call time.
    """
    dp = rules.dp_axes(mesh)
    manual_axes = set(dp)

    def train_step_acis(state: TrainState, batch
                        ) -> tuple[TrainState, dict]:
        def local(params, opt, step, residual, arenas, tokens, context):
            b = {"tokens": tokens}
            if context is not None:
                b["context"] = context
            with jax.named_scope("train.fwd_bwd"):
                grads, metrics = _accumulate_grads(
                    model, params, b, microbatches, None)
            with jax.named_scope("train.grad_sync"):
                if arenas is not None:
                    synced, new_residual, new_arenas = engine.gradient_sync(
                        grads, residual, arenas=arenas)
                else:
                    synced, new_residual = engine.gradient_sync(grads,
                                                                residual)
                    new_arenas = None
            with jax.named_scope("train.optimizer"):
                new_params, new_opt = optimizer.update(synced, opt, params,
                                                       step)
            metrics = jax.tree.map(
                lambda x: jax.lax.pmean(x, dp), metrics)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree.leaves(synced)))
            metrics["grad_norm"] = gn
            return new_params, new_opt, new_residual, new_arenas, metrics

        tokens = batch["tokens"]
        context = batch.get("context")
        in_specs = (P(), P(), P(), P(), P(), P(dp), P(dp))
        out_specs = (P(), P(), P(), P(), P())
        if context is None:
            fn = lambda p, o, s, r, a, t: local(p, o, s, r, a, t, None)
            in_specs = in_specs[:6]
        else:
            fn = local
        mapped = jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=manual_axes, check_vma=False)
        args = (state.params, state.opt, state.step, state.ef_residual,
                state.sync_arenas, tokens) \
            + (() if context is None else (context,))
        new_params, new_opt, new_residual, new_arenas, metrics = \
            mapped(*args)
        return TrainState(new_params, new_opt, state.step + 1,
                          new_residual, new_arenas), metrics

    jitted = jax.jit(train_step_acis, donate_argnums=(0,) if donate else ())

    @functools.wraps(jitted)
    def timed(state, batch):
        rec = recorder if recorder is not None else _obs.RECORDER
        if not rec.enabled:
            return jitted(state, batch)
        import time
        t0 = time.perf_counter()
        out = jax.block_until_ready(jitted(state, batch))
        rec.count("train.steps")
        rec.observe("train.step_s", time.perf_counter() - t0)
        return out

    return timed


def init_state(model: Model, optimizer: Optimizer, key,
               engine: Optional[CollectiveEngine] = None, *,
               mesh: Optional[Mesh] = None,
               arenas: bool = False,
               microbatches: int = 1) -> TrainState:
    """``arenas=True`` (acis backends, ``mesh`` required) additionally
    allocates the persistent gradient-sync bucket arenas so the step can
    write bucket packs in place — pair with
    ``build_train_step_acis(..., donate=True)``.  Pass the step's
    ``microbatches`` too: it decides the grad dtypes the arenas must
    match (accumulated grads are f32, single-microbatch grads carry the
    param dtype)."""
    params = model.init(key)
    opt = optimizer.init(params)
    residual = None
    sync_arenas = None
    if engine is not None and engine.config.backend != "xla":
        residual = engine.init_state(params)
        if arenas:
            if mesh is None:
                raise ValueError("init_state(arenas=True) needs mesh= — "
                                 "bucket boundaries depend on the DP "
                                 "ring sizes")
            sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            axis_sizes = {a: sizes[a]
                          for a in (engine.inner_axis, engine.outer_axis)
                          if a is not None and a in sizes}
            grads_like = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(
                    p.shape,
                    jnp.float32 if microbatches > 1 else p.dtype),
                params)
            sync_arenas = engine.init_arenas(grads_like,
                                             axis_sizes=axis_sizes)
    return TrainState(params, opt, jnp.zeros((), jnp.int32), residual,
                      sync_arenas)
