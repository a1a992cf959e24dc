"""Pallas TPU kernels: chunked scans (prefix sum + RG-LRU linear recurrence).

Two recurrences power the Type 3 "look-aside loop" collectives and the
SSM/hybrid architectures:

  * ``prefix_sum``  — h_t = h_{t-1} + x_t         (Fig. 5 op)
  * ``rglru_scan``  — h_t = a_t ⊙ h_{t-1} + b_t   (RecurrentGemma RG-LRU)

Tiling: grid (feature blocks, time chunks).  Time is the inner, sequential
grid axis; the carry lives in a VMEM scratch buffer that persists across
its steps — exactly the paper's "state within the operation" — and resets
at the first chunk of each feature block.  Feature blocks of at most
``BLOCK_D`` lanes keep VMEM bounded at any width (a 4096-wide RG-LRU in one
block overflows a v5e core's VMEM).  Within a chunk the scan is computed
with a log-step Hillis-Steele over the time axis (vector ops on the lane
dim), so the sequential dependency is only chunk-to-chunk.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _interpret_default

CHUNK_T = 256
BLOCK_D = 512


def _log_steps(n: int) -> list[int]:
    steps, k = [], 1
    while k < n:
        steps.append(k)
        k *= 2
    return steps


def _tiles(t: int, d: int) -> tuple[int, int, int, int]:
    """(time chunk, time pad, feature block, feature pad) for [t, d]."""
    chunk = min(CHUNK_T, t)
    dblk = min(BLOCK_D, d)
    return chunk, (-t) % chunk, dblk, (-d) % dblk


def _scan_call(kernel, out_dtype, carry_dtype, chunk, dblk, *xs,
               interpret):
    t, d = xs[0].shape
    spec = pl.BlockSpec((chunk, dblk), lambda i, j: (j, i))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((t, d), out_dtype),
        grid=(d // dblk, t // chunk),
        in_specs=[spec] * len(xs),
        out_specs=spec,
        scratch_shapes=[pltpu_vmem((1, dblk), carry_dtype)],
        name="chunk_scan",
        interpret=_interpret_default() if interpret is None else interpret,
    )(*xs)


# ---------------------------------------------------------------------------
# prefix sum
# ---------------------------------------------------------------------------

def _prefix_kernel(x_ref, o_ref, carry_ref, *, chunk_t: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    x = x_ref[...]                     # [chunk_t, D]
    # intra-chunk inclusive scan (log-step over time)
    for k in _log_steps(chunk_t):
        x = x + jnp.pad(x, ((k, 0), (0, 0)))[:chunk_t]
    out = x + carry_ref[...]
    o_ref[...] = out
    carry_ref[...] = out[-1:, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def prefix_sum(x: jax.Array, *,
               interpret: Optional[bool] = None) -> jax.Array:
    """Inclusive prefix sum over axis 0 of [T] or [T, D] arrays."""
    squeeze = x.ndim == 1
    x2 = x[:, None] if squeeze else x
    t, d = x2.shape
    chunk, tpad, dblk, dpad = _tiles(t, d)
    x2 = jnp.pad(x2, ((0, tpad), (0, dpad)))
    out = _scan_call(functools.partial(_prefix_kernel, chunk_t=chunk),
                     x2.dtype, x2.dtype, chunk, dblk, x2,
                     interpret=interpret)[:t, :d]
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# RG-LRU gated recurrence  h_t = a_t * h_{t-1} + b_t
# ---------------------------------------------------------------------------

def _rglru_kernel(a_ref, b_ref, o_ref, carry_ref, *, chunk_t: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        carry_ref[...] = jnp.zeros_like(carry_ref)

    a = a_ref[...].astype(jnp.float32)   # [chunk_t, D]
    h = b_ref[...].astype(jnp.float32)
    # Blelloch-free log-step scan of the affine recurrence:
    # pair (a, h) composes as (a2*a1, a2*h1 + h2)
    for k in _log_steps(chunk_t):
        a_prev = jnp.pad(a, ((k, 0), (0, 0)), constant_values=1.0)[:chunk_t]
        h_prev = jnp.pad(h, ((k, 0), (0, 0)))[:chunk_t]
        h = a * h_prev + h
        a = a * a_prev
    out = h + a * carry_ref[...]
    o_ref[...] = out.astype(o_ref.dtype)
    carry_ref[...] = out[-1:, :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def rglru_scan(a: jax.Array, b: jax.Array, *,
               interpret: Optional[bool] = None) -> jax.Array:
    """h_t = a_t * h_{t-1} + b_t over [T, D] inputs (h_0 = 0)."""
    if a.shape != b.shape or a.ndim != 2:
        raise ValueError(f"bad shapes {a.shape} {b.shape}")
    t, d = a.shape
    chunk, tpad, dblk, dpad = _tiles(t, d)
    a = jnp.pad(a, ((0, tpad), (0, dpad)), constant_values=1)
    b = jnp.pad(b, ((0, tpad), (0, dpad)))
    return _scan_call(functools.partial(_rglru_kernel, chunk_t=chunk),
                      jnp.float32, jnp.float32, chunk, dblk, a, b,
                      interpret=interpret)[:t, :d]


def pltpu_vmem(shape, dtype):
    """VMEM scratch allocation (portable across pallas versions)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, dtype)
