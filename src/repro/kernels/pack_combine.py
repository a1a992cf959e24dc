"""Fused bucket pack (+ optional combine) writing in place into an arena.

The Coalesce pass packs N gradient leaves into one flat bucket before the
ring collective; the emitted default path is one ``dynamic_update_slice``
per leaf — N small XLA kernels and a full copy of the arena per leaf at
worst.  This kernel lowers the whole pack to **one** Pallas launch whose
output aliases the arena input (``input_output_aliases={0: 0}``): with the
arena donated at the jit boundary the leaves land in place, no transient.

``op`` additionally fuses the per-hop combine into the same launch
(``arena[seg] = combine(arena[seg], leaf)``) — the pack+combine round trip
of a ring hop (combine → copy → slice) collapses to one kernel.

Tiling: the grid walks the arena in ``BLOCK``-element blocks, so VMEM holds
one arena block plus, per leaf, at most two leaf blocks — independent of
the bucket size.  Leaves sit at arbitrary (ragged) offsets, but TPU
memory only moves tile-aligned windows, so a leaf is never sliced at its
arena offset.  A leaf of at least one block is read as its own aligned
blocks ``q`` and ``q + 1``; the arena block ``b`` then starts at the
*static* lane ``-offset mod BLOCK`` of that pair, so one static unaligned
load from a 32-bit work buffer shifts it into place.  A shorter leaf is
resident whole and lands in the (statically known) one or two blocks it
touches.  Lanes outside the leaf are masked, so the arena's other
contents — including a tail past ``sum(sizes)`` — survive.  The work
buffer is 32-bit because packed 16/8-bit vectors cannot be stored at an
unaligned lane; the round trip through it is exact.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import _interpret_default

# arena elements per grid step: a multiple of every dtype's 1-D HBM tile
# (1024 lanes × up to 4 packed int8) and 32 KiB of f32 VMEM per buffer
BLOCK = 8192

_COMBINE = {
    "add": jnp.add,
    "max": jnp.maximum,
    "min": jnp.minimum,
}


def _pack_kernel(a_ref, *refs, layout, op):
    o_ref, buf = refs[-2], refs[-1]
    b = pl.program_id(0)
    o_ref[...] = a_ref[...]
    pos = b * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (BLOCK,), 0)

    def put(vals, off, size):
        old = o_ref[...]
        new = vals if op is None else _COMBINE[op](old.astype(buf.dtype),
                                                   vals)
        live = (pos >= off) & (pos < off + size)
        o_ref[...] = jnp.where(live, new.astype(old.dtype), old)

    i = 0
    for off, size in layout:
        if size >= BLOCK:
            lo, hi = refs[i], refs[i + 1]
            i += 2
            shift = -off % BLOCK

            @pl.when((b * BLOCK < off + size) & ((b + 1) * BLOCK > off))
            def _(lo=lo, hi=hi, shift=shift, off=off, size=size):
                buf[0:BLOCK] = lo[...].astype(buf.dtype)
                buf[BLOCK:2 * BLOCK] = hi[...].astype(buf.dtype)
                put(buf[shift:shift + BLOCK], off, size)
        else:
            part = refs[i]
            i += 1
            for blk in range(off // BLOCK, (off + size - 1) // BLOCK + 1):
                start = off - (blk - 1) * BLOCK      # in (0, 2 * BLOCK)

                @pl.when(b == blk)
                def _(part=part, start=start, off=off, size=size):
                    buf[start:start + size] = part[...].astype(buf.dtype)
                    put(buf[BLOCK:2 * BLOCK], off, size)


def _leaf_specs(off: int, size: int) -> list[pl.BlockSpec]:
    if size < BLOCK:
        return [pl.BlockSpec((size,), lambda b: (0,))]
    first, last = -(-off // BLOCK), -(-size // BLOCK) - 1

    def block(q):
        return jnp.clip(q, 0, last)
    return [pl.BlockSpec((BLOCK,), lambda b: (block(b - first),)),
            pl.BlockSpec((BLOCK,), lambda b: (block(b - first + 1),))]


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def fused_pack(arena: jax.Array, *parts: jax.Array,
               op: Optional[str] = None,
               interpret: Optional[bool] = None) -> jax.Array:
    """Write ``parts`` (flat, pre-cast to the arena dtype) into ``arena``
    back to back, in one Pallas launch aliased onto the arena buffer.

    ``op=None`` is the pure pack; ``op in {"add", "max", "min"}`` combines
    each part into the arena's current segment contents instead (the fused
    pack+combine hop).  Returns the updated arena.  ``interpret=None``
    follows the backend (:func:`repro.kernels._interpret_default`).
    """
    if not parts:
        return arena
    if interpret is None:
        interpret = _interpret_default()
    sizes = tuple(int(p.shape[0]) for p in parts)
    n = arena.shape[0]
    if sum(sizes) > n:
        raise ValueError(
            f"pack of {sum(sizes)} elements overflows arena of {n}")
    if n < BLOCK:
        # a sub-block arena is not a whole number of tiles: pack into one
        # padded block (a copy of < BLOCK elements, not in place)
        padded = jnp.pad(arena, (0, BLOCK - n))
        return fused_pack(padded, *parts, op=op, interpret=interpret)[:n]
    layout, specs, args, off = [], [pl.BlockSpec((BLOCK,), lambda b: (b,))], \
        [arena], 0
    for p, s in zip(parts, sizes):
        layout.append((off, s))
        leaf = _leaf_specs(off, s)
        specs += leaf
        args += [p] * len(leaf)
        off += s
    work = jnp.float32 if jnp.issubdtype(arena.dtype, jnp.floating) \
        else jnp.int32
    return pl.pallas_call(
        functools.partial(_pack_kernel, layout=tuple(layout), op=op),
        out_shape=jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        grid=(-(-n // BLOCK),),
        in_specs=specs,
        out_specs=pl.BlockSpec((BLOCK,), lambda b: (b,)),
        scratch_shapes=[pltpu.VMEM((3 * BLOCK,), work)],
        input_output_aliases={0: 0},
        name="fused_pack",
        interpret=interpret,
    )(*args)


def pack_parts(xs: Sequence[jax.Array], dtype) -> list[jax.Array]:
    """Flatten + cast leaves to the arena's flat dtype (the pre-kernel
    normalization both the kernel and its oracle share)."""
    return [x.reshape(-1).astype(dtype) for x in xs]
