"""Pallas TPU kernel: per-hop reduce combine (the switch aggregation unit).

The hot inner loop of every ACiS reduction schedule is ``combine(incoming,
local)`` applied to a hop-sized message.  On the FPGA this is the
programmable aggregation unit; on TPU it is a VPU-elementwise kernel that
should run at HBM bandwidth.  Tiling: the flat message is viewed as
[rows, 128] (lane-aligned) and blocked (BLOCK_ROWS, 128) into VMEM — three
resident blocks (x, y, out) of (512, 128) f32 = 768 KB, comfortably inside
a v5e core's VMEM while deep enough to amortize grid overhead.

Supported ops: add | max | min | mac(alpha) — the Type 1 fixed set plus the
paper's fused multiply-accumulate example.  ``alpha`` is a compile-time
constant (it is a schedule parameter, not data).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _interpret_default

LANES = 128
BLOCK_ROWS = 512

_OPS = {
    "add": lambda x, y: x + y,
    "max": jnp.maximum,
    "min": jnp.minimum,
}


def _combine_kernel(x_ref, y_ref, o_ref, *, op: str, alpha: float):
    x = x_ref[...]
    y = y_ref[...]
    if op == "mac":
        o_ref[...] = x + jnp.asarray(alpha, x.dtype) * y
    else:
        o_ref[...] = _OPS[op](x, y)


def _pad_rows(flat: jax.Array) -> tuple[jax.Array, int]:
    size = flat.shape[0]
    rem = (-size) % LANES
    if rem:
        flat = jnp.concatenate([flat, jnp.zeros((rem,), flat.dtype)])
    return flat.reshape(-1, LANES), size


@functools.partial(jax.jit, static_argnames=("op", "alpha", "interpret"))
def fused_combine(x: jax.Array, y: jax.Array, *, op: str = "add",
                  alpha: float = 1.0,
                  interpret: Optional[bool] = None) -> jax.Array:
    """combine(x, y) elementwise over arbitrary-shape operands."""
    if x.shape != y.shape:
        raise ValueError(f"shape mismatch {x.shape} vs {y.shape}")
    shape, dtype = x.shape, x.dtype
    x2, size = _pad_rows(x.reshape(-1))
    y2, _ = _pad_rows(y.reshape(-1))
    rows = x2.shape[0]
    block_rows = min(BLOCK_ROWS, rows)
    # pad rows to a multiple of the block
    rpad = (-rows) % block_rows
    if rpad:
        zpad = jnp.zeros((rpad, LANES), dtype)
        x2 = jnp.concatenate([x2, zpad])
        y2 = jnp.concatenate([y2, zpad])
    grid = (x2.shape[0] // block_rows,)

    out = pl.pallas_call(
        functools.partial(_combine_kernel, op=op, alpha=alpha),
        out_shape=jax.ShapeDtypeStruct(x2.shape, dtype),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),
        name="fused_combine",
        interpret=_interpret_default() if interpret is None else interpret,
    )(x2, y2)
    return out.reshape(-1)[:size].reshape(shape)
