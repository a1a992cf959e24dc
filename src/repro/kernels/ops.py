"""Jit'd public wrappers for the Pallas kernels.

``interpret`` is True on the CPU backend (kernel bodies execute in Python
for correctness validation) and False everywhere else, where
`pl.pallas_call` compiles to Mosaic.  It is decided here, outside each
kernel's jit, so the kernel's trace cache never serves one backend's
lowering to the other.  Each wrapper is the drop-in, signature-compatible
implementation of its `repro.kernels.ref` oracle.
"""

from __future__ import annotations

from repro.kernels import _interpret_default
from repro.kernels import fused_combine as _fc
from repro.kernels import pack_combine as _pc
from repro.kernels import quant_combine as _qc
from repro.kernels import topk_accum as _ta
from repro.kernels import chunk_scan as _cs
from repro.kernels import rwkv6_recurrence as _rw


def combine_add(x, y):
    return _fc.fused_combine(x, y, op="add", interpret=_interpret_default())


def combine_max(x, y):
    return _fc.fused_combine(x, y, op="max", interpret=_interpret_default())


def combine_min(x, y):
    return _fc.fused_combine(x, y, op="min", interpret=_interpret_default())


def combine_mac(acc, x, alpha: float = 1.0):
    return _fc.fused_combine(acc, x, op="mac", alpha=float(alpha),
                             interpret=_interpret_default())


def pack_combine(arena, *parts, op=None):
    return _pc.fused_pack(arena, *parts, op=op,
                          interpret=_interpret_default())


def quant_combine(qa, sa, qb, sb):
    return _qc.quant_combine(qa, sa, qb, sb, interpret=_interpret_default())


def topk_accumulate(dense, idx, vals):
    return _ta.topk_accumulate(dense, idx, vals,
                               interpret=_interpret_default())


def prefix_sum(x):
    return _cs.prefix_sum(x, interpret=_interpret_default())


def rglru_scan(a, b):
    return _cs.rglru_scan(a, b, interpret=_interpret_default())


def rwkv6_recurrence(r, k, v, w, u):
    return _rw.rwkv6_recurrence(r, k, v, w, u,
                                interpret=_interpret_default())
