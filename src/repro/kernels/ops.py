"""Public entry points of the Pallas kernels.

Each kernel runs natively on a TPU and in the Pallas interpreter elsewhere
(``interpret=None`` resolves through ``kernels.resolve_interpret``), so the
CPU test suite checks every code path against the ``ref.py`` oracles.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from . import ref  # noqa: F401  (re-exported for tests/benchmarks)
from .bsr_spgemm import bsr_spgemm, bsr_spgemm_schedule  # noqa: F401
from .flash_attention import attention_block_schedule  # noqa: F401
from .flash_attention import flash_attention  # noqa: F401
from .moe_gemm import moe_gemm, moe_gemm_schedule  # noqa: F401
from .rwkv6_scan import rwkv6  # noqa: F401


def bsr_spmm(x, w_blocks, sched, *, n_j_blocks: int, bt: int = 128,
             interpret: Optional[bool] = None):
    """Structured-sparse weight matmul (schedule from inspect_bsr_weight)."""
    from .bsr_spmm import bsr_spmm as _bsr_spmm
    return _bsr_spmm(x, w_blocks, jnp.asarray(sched["w_id"]),
                     jnp.asarray(sched["k_blk"]), jnp.asarray(sched["j_blk"]),
                     jnp.asarray(sched["is_first"]),
                     jnp.asarray(sched["is_last"]),
                     n_j_blocks=n_j_blocks, bt=bt, interpret=interpret)
