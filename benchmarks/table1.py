"""Table I of the paper: the 24 SuiteSparse matrices, as synthetic
stand-ins (offline container — no downloads).

Each entry reproduces the published (rows, nnz, density) statistics with a
structure pattern matched to the matrix's domain (FEM → banded/blocky,
graphs/chemistry → powerlaw/uniform).  Large instances are scaled down by
``scale`` (rows÷k, nnz÷k: preserves nnz/row, hence partial products per
nnz) to keep the single-core container runtime sane; the analytic
simulator receives the ORIGINAL density so the CPU locality model sees the
published operating point.  Speedups are ratios of pp-proportional times
and are insensitive to the scale factor.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from repro.core import random_csr
from repro.core.formats import random_spd_csr


@dataclasses.dataclass(frozen=True)
class MatrixSpec:
    name: str
    spgemm_id: Optional[str]
    chol_id: Optional[str]
    rows: int
    nnz: int
    pattern: str

    @property
    def density(self) -> float:
        return self.nnz / (self.rows * float(self.rows))

    @property
    def nnz_per_row(self) -> float:
        return self.nnz / self.rows


TABLE1 = [
    MatrixSpec("mario_002", "S1", None, 389_000, 2_100_000, "banded"),
    MatrixSpec("m133-b3", "S2", None, 200_000, 800_000, "uniform"),
    MatrixSpec("filter3D", "S3", None, 106_000, 2_700_000, "banded"),
    MatrixSpec("cop20K", "S4", None, 121_000, 2_600_000, "powerlaw"),
    MatrixSpec("offshore", "S5", None, 259_000, 4_200_000, "banded"),
    MatrixSpec("poission3Da", "S6", None, 13_000, 352_000, "banded"),
    MatrixSpec("cage12", "S7", None, 130_000, 2_000_000, "uniform"),
    MatrixSpec("2cubes_sphere", "S8", None, 101_000, 1_640_000, "banded"),
    MatrixSpec("bcsstk13", "S9", "C2", 2_000, 83_000, "blocky"),
    MatrixSpec("bcsstk17", "S10", "C3", 10_000, 428_000, "blocky"),
    MatrixSpec("cant", "S11", "C4", 62_000, 4_000_000, "blocky"),
    MatrixSpec("consph", "S12", None, 83_000, 6_000_000, "blocky"),
    MatrixSpec("mbeacxc", "S13", None, 496, 49_000, "uniform"),
    MatrixSpec("pdb1HYs", "S14", None, 36_000, 4_300_000, "blocky"),
    MatrixSpec("rma10", "S15", None, 46_000, 2_300_000, "blocky"),
    MatrixSpec("descriptor_xingo6u", "S16", None, 20_000, 73_000, "powerlaw"),
    MatrixSpec("g7jac060sc", "S17", None, 17_000, 203_000, "powerlaw"),
    MatrixSpec("ns3Da", "S18", None, 20_000, 1_600_000, "uniform"),
    MatrixSpec("TSOPF_RS_b162_c3", "S19", None, 15_000, 610_000, "blocky"),
    MatrixSpec("cbuckle", "S20", "C6", 13_000, 676_000, "banded"),
    MatrixSpec("Pre_poisson", None, "C1", 12_000, 715_000, "banded"),
    MatrixSpec("gyro", None, "C5", 17_000, 1_000_000, "banded"),
    MatrixSpec("bcsstk18", None, "C7", 11_000, 80_000, "banded"),
    MatrixSpec("bcsstk36", None, "C8", 23_000, 1_100_000, "banded"),
]

SPGEMM_SET = [m for m in TABLE1 if m.spgemm_id]
CHOLESKY_SET = [m for m in TABLE1 if m.chol_id]

MAX_PP = 25_000_000      # cap on partial products for the measured path
MAX_ROWS = 64_000
CHOL_MAX_ROWS = 6_000    # symbolic pass is a host python walk


def spgemm_scale(spec: MatrixSpec) -> int:
    pp_est = spec.nnz * spec.nnz_per_row
    k = max(1, int(np.ceil(pp_est / MAX_PP)),
            int(np.ceil(spec.rows / MAX_ROWS)))
    return k


def make_spgemm_matrix(spec: MatrixSpec, seed: int = 0,
                       k: Optional[int] = None):
    """Stand-in for ``spec`` with rows and nnz divided by ``k`` (default:
    ``spgemm_scale``; ``k=1`` is the published size).  Returns (A, k)."""
    k = spgemm_scale(spec) if k is None else k
    rows, nnz = max(64, spec.rows // k), max(128, spec.nnz // k)
    rng = np.random.default_rng(seed)
    a = random_csr(rows, rows, nnz / (rows * float(rows)), rng, spec.pattern)
    return a, k


def chol_scale(spec: MatrixSpec) -> int:
    return max(1, int(np.ceil(spec.rows / CHOL_MAX_ROWS)))


def make_chol_matrix(spec: MatrixSpec, seed: int = 0,
                     k: Optional[int] = None):
    """SPD stand-in for ``spec`` with rows and nnz divided by ``k``
    (default: ``chol_scale``; ``k=1`` is the published size)."""
    k = chol_scale(spec) if k is None else k
    rows = max(64, spec.rows // k)
    nnz = max(128, spec.nnz // k)
    rng = np.random.default_rng(seed)
    a = random_spd_csr(rows, nnz / (rows * float(rows)), rng, "banded")
    return a, k
