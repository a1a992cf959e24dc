"""Pallas TPU kernel: sparse (idx, val) scatter-accumulate.

HW adaptation note (DESIGN.md §2): the FPGA switch scatter-accumulates with
an addressable BRAM; TPUs have no gather/scatter unit, so the TPU-native
formulation is a **one-hot MXU matmul**: for each dense block, accumulate
``vals @ onehot(idx ∈ block)`` — K·B MACs on the systolic array instead of K
random HBM touches.  For the top-k regimes the sparse collective targets
(K ≤ 1% of size) this is far below the HBM roofline of the dense
alternative and has fully regular memory traffic.

Tiling: dense is viewed [S] → [nblk, BLOCK_S]; grid over nblk; idx/vals are
small and VMEM-resident for every grid step (BlockSpec maps them whole).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _interpret_default

BLOCK_S = 2048


def _topk_accum_kernel(dense_ref, idx_ref, vals_ref, o_ref, *, block_s: int):
    blk = pl.program_id(0)
    base = blk * block_s
    idx = idx_ref[...]                    # [K] int32 (whole payload)
    vals = vals_ref[...]                  # [K] f32
    pos = jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], block_s), 1)
    local = idx[:, None] - base           # [K, block_s] target offsets
    onehot = (local == pos).astype(vals.dtype)
    contrib = vals[None, :] @ onehot      # [1, block_s] on the MXU
    o_ref[...] = dense_ref[...] + contrib[0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_accumulate(dense: jax.Array, idx: jax.Array, vals: jax.Array, *,
                    interpret: Optional[bool] = None) -> jax.Array:
    """dense[idx] += vals (duplicates accumulate). dense: [S] f32/bf16."""
    s = dense.shape[0]
    pad = (-s) % BLOCK_S
    d = jnp.concatenate([dense, jnp.zeros((pad,), dense.dtype)]) if pad else dense
    nblk = d.shape[0] // BLOCK_S

    out = pl.pallas_call(
        functools.partial(_topk_accum_kernel, block_s=BLOCK_S),
        out_shape=jax.ShapeDtypeStruct(d.shape, d.dtype),
        grid=(nblk,),
        in_specs=[
            pl.BlockSpec((BLOCK_S,), lambda i: (i,)),
            pl.BlockSpec(idx.shape, lambda i: (0,)),   # whole payload
            pl.BlockSpec(vals.shape, lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((BLOCK_S,), lambda i: (i,)),
        name="topk_accum",
        interpret=_interpret_default() if interpret is None else interpret,
    )(d, idx, vals.astype(dense.dtype))
    return out[:s]
