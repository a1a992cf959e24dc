"""Pallas kernels of the switch data path (``ops.py`` wrappers, ``ref.py``
oracles).

A kernel compiles to Mosaic on every accelerator backend and runs in the
Pallas interpreter only on the CPU backend, where tier-1 validates its
numerics.  There is no override: a kernel that runs on a TPU is compiled.
"""

import jax


def _interpret_default() -> bool:
    # asked per call, never cached: tests steer jax.default_backend
    return jax.default_backend() == "cpu"
