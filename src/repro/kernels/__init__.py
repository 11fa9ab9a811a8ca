"""Pallas TPU kernels of the REAP executors (see ``ops.py`` for the wrappers).

Every kernel entry point takes ``interpret=None`` by default and resolves it
through :func:`resolve_interpret` — the one backend auto-detection in this
package: the native Mosaic kernel on a TPU, the Pallas interpreter on any
other backend (the CPU test suite).  Pass ``interpret=True``/``False`` to
force a mode.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

#: Block index 0 for BlockSpec index maps.  A bare ``0`` traces as int64
#: when ``jax_enable_x64`` is on, and Mosaic rejects such an index map.
I0 = np.int32(0)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` → interpret mode exactly when JAX's default backend is not a
    TPU; an explicit bool passes through."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def dot_precision(dtype):
    """Precision of a tile dot on ``dtype`` operands: ``HIGHEST`` for
    float32, whose operands the MXU otherwise rounds to bfloat16 (~1e-3
    relative error on a TPU v5e); the default for narrower types, where
    Mosaic accepts no other."""
    return jax.lax.Precision.HIGHEST if dtype == np.float32 else None
