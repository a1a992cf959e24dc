"""Pallas TPU kernel: encoded-domain int8 combine (dequant-add-requant).

The in-switch program for the quantized wire format: two int8 payloads and
their per-block scales come in, one goes out — in a single VMEM pass, so the
decoded f32 intermediates never touch HBM.  This is the aggregation-unit
configuration the paper's Type 2 uses for "sparse/quantized user datatypes".

Layout: payloads are [B, QBLOCK(=256)] int8 rows with scales [B, 1] f32.
Block tiling (64, 256): int8 ops in VMEM, rowwise absmax on the VPU, requant
and emit.  Six resident blocks (qa, qb, sa, sb, qo, so) ≈ 64·256·(1+1+1)B +
small — trivially VMEM-resident; the kernel is HBM-bandwidth-bound, which is
the point: wire bytes = HBM bytes = 1/4 of the f32 stream.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _interpret_default

QBLOCK = 256
BLOCK_B = 64


def _quant_combine_kernel(qa_ref, sa_ref, qb_ref, sb_ref, qo_ref, so_ref):
    acc = (qa_ref[...].astype(jnp.float32) * sa_ref[...] +
           qb_ref[...].astype(jnp.float32) * sb_ref[...])
    absmax = jnp.max(jnp.abs(acc), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    qo_ref[...] = jnp.clip(jnp.round(acc / scale), -127, 127).astype(jnp.int8)
    so_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_combine(qa: jax.Array, sa: jax.Array, qb: jax.Array,
                  sb: jax.Array, *, interpret: Optional[bool] = None
                  ) -> tuple[jax.Array, jax.Array]:
    """Combine blockwise-int8 payloads (q: [B, QBLOCK] int8, s: [B] f32)."""
    if qa.shape != qb.shape or qa.shape[1] != QBLOCK:
        raise ValueError(f"bad payload shapes {qa.shape} {qb.shape}")
    b = qa.shape[0]
    sa2 = sa.reshape(b, 1)
    sb2 = sb.reshape(b, 1)
    block_b = min(BLOCK_B, b)
    pad = (-b) % block_b
    if pad:
        qa = jnp.concatenate([qa, jnp.zeros((pad, QBLOCK), qa.dtype)])
        qb = jnp.concatenate([qb, jnp.zeros((pad, QBLOCK), qb.dtype)])
        sa2 = jnp.concatenate([sa2, jnp.ones((pad, 1), sa2.dtype)])
        sb2 = jnp.concatenate([sb2, jnp.ones((pad, 1), sb2.dtype)])
    grid = ((b + pad) // block_b,)

    qo, so = pl.pallas_call(
        _quant_combine_kernel,
        out_shape=(jax.ShapeDtypeStruct(qa.shape, jnp.int8),
                   jax.ShapeDtypeStruct(sa2.shape, jnp.float32)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, QBLOCK), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ],
        out_specs=(pl.BlockSpec((block_b, QBLOCK), lambda i: (i, 0)),
                   pl.BlockSpec((block_b, 1), lambda i: (i, 0))),
        name="quant_combine",
        interpret=_interpret_default() if interpret is None else interpret,
    )(qa, sa2, qb, sb2)
    return qo[:b], so[:b, 0]
