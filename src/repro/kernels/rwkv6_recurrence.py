"""Pallas TPU kernel: RWKV-6 "Finch" WKV recurrence (data-dependent decay).

State S[K, V] per head is the densest look-aside memory in the assigned
architecture pool: it must be read+updated every token.  Tiling: grid =
(heads, time-chunks); time chunks are sequential (TPU grid order), the state
lives in a VMEM scratch that persists across the chunk dimension and resets
at chunk 0 of each head.  Within a chunk the recurrence is stepped on the
VPU ([K,V] FMA per token) — the numerically safe form for arbitrary decays
(the chunked-matmul form divides by cumulative decay products and can
overflow f32 for long chunks; see models/rwkv6.py for the MXU training path
with sub-chunked log-space handling).

Per head h, token t:
    kv   = k_t ⊗ v_t
    o_t  = Σ_k r_t[k] · (S[k,:] + u[k]·kv[k,:])
    S    = diag(w_t) S + kv
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _interpret_default

CHUNK_T = 64


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, sout_ref, s_ref,
                x_ref, v32_ref, o32_ref, *, chunk_t: int):
    # NOTE: positional order is (inputs..., outputs..., scratch...).
    @pl.when(pl.program_id(1) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    # Mosaic slices values only statically and has no cheap [1, K] ->
    # [K, 1] relayout: token rows are read from the f32 staging buffers
    # at a dynamic sublane, and a row becomes a column by a masked lane
    # reduction against the identity.
    x_ref[0] = r_ref[0].astype(jnp.float32)
    x_ref[1] = k_ref[0].astype(jnp.float32)
    x_ref[2] = w_ref[0].astype(jnp.float32)
    v32_ref[...] = v_ref[0].astype(jnp.float32)
    kk = x_ref.shape[2]
    eye = jax.lax.broadcasted_iota(jnp.int32, (kk, kk), 0) == \
        jax.lax.broadcasted_iota(jnp.int32, (kk, kk), 1)

    def col(row):                          # [1, K] -> [K, 1]
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    u = col(u_ref[0].astype(jnp.float32))  # [K, 1]

    def step(t, s):
        row = pl.ds(t, 1)
        kv = col(x_ref[1, row, :]) * v32_ref[row, :]       # [K, V]
        o32_ref[row, :] = ((s + u * kv) * col(x_ref[0, row, :])).sum(
            axis=0, keepdims=True)                          # [1, V]
        return col(x_ref[2, row, :]) * s + kv

    s = jax.lax.fori_loop(0, chunk_t, step, s_ref[...])
    o_ref[0] = o32_ref[...].astype(o_ref.dtype)
    s_ref[...] = s
    sout_ref[...] = s[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def rwkv6_recurrence(r: jax.Array, k: jax.Array, v: jax.Array,
                     w: jax.Array, u: jax.Array, *,
                     interpret: Optional[bool] = None
                     ) -> tuple[jax.Array, jax.Array]:
    """Multi-head WKV6.

    r, k, w: [H, T, K]; v: [H, T, V]; u: [H, K].
    Returns (o: [H, T, V], s_final: [H, K, V]).
    """
    h, t, kk = r.shape
    vv = v.shape[2]
    chunk = min(CHUNK_T, t)
    pad = (-t) % chunk
    if pad:
        zr = jnp.zeros((h, pad, kk), r.dtype)
        r = jnp.concatenate([r, zr], axis=1)
        k = jnp.concatenate([k, zr.astype(k.dtype)], axis=1)
        w = jnp.concatenate([w, jnp.ones((h, pad, kk), w.dtype)], axis=1)
        v = jnp.concatenate([v, jnp.zeros((h, pad, vv), v.dtype)], axis=1)
    tp = t + pad
    u2 = u[:, None, :]  # [H, 1, K]

    o, s_final = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk_t=chunk),
        out_shape=(jax.ShapeDtypeStruct((h, tp, vv), v.dtype),
                   jax.ShapeDtypeStruct((h, kk, vv), jnp.float32)),
        grid=(h, tp // chunk),
        in_specs=[
            pl.BlockSpec((1, chunk, kk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, kk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, vv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, kk), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, kk), lambda i, j: (i, 0, 0)),
        ],
        out_specs=(pl.BlockSpec((1, chunk, vv), lambda i, j: (i, j, 0)),
                   pl.BlockSpec((1, kk, vv), lambda i, j: (i, 0, 0))),
        scratch_shapes=[_vmem((kk, vv), jnp.float32),
                        _vmem((3, chunk, kk), jnp.float32),
                        _vmem((chunk, vv), jnp.float32),
                        _vmem((chunk, vv), jnp.float32)],
        name="rwkv6_recurrence",
        interpret=_interpret_default() if interpret is None else interpret,
    )(r, k, v, w, u2)
    return o[:, :t], s_final


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
