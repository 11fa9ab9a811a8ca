"""Seeded stand-ins for the SuiteSparse matrices of the benchmark.

Each generator returns a symmetric sparsity pattern ``(indptr, indices)``
(CSR, int64, columns sorted within a row) with exactly the row and nonzero
counts that its configuration file states.  Values are drawn separately,
from the run's seed, by :func:`normal_values` and :func:`spd_values`, so
one pattern serves a stream of fresh value arrays.

These are the benchmark's own copies: later changes to the program's
generators cannot move the yardstick.
"""
from __future__ import annotations

import numpy as np


def _csr_from_pairs(n: int, row: np.ndarray, col: np.ndarray):
    """Sorted CSR pattern of distinct ``(row, col)`` pairs."""
    key = np.unique(row.astype(np.int64) * n + col.astype(np.int64))
    r, c = key // n, key % n
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(r, minlength=n), out=indptr[1:])
    return indptr, c.astype(np.int64)


def fem_node_mesh(rng: np.random.Generator, *, rows: int, nnz: int,
                  grid, dofs_per_node: int):
    """Pattern of a node-numbered 3-D finite-element mesh.

    Nodes sit on a ``grid = (nx, ny, nz)`` lattice, numbered x fastest, and
    each couples to its 26 lattice neighbours (a 27-point stencil).  Every
    coupled node pair is a dense ``dofs × dofs`` block, so nonzeros cluster
    in small dense blocks along a band of ``nx·ny + nx + 1`` nodes.  Node
    pairs are dropped at random, symmetrically, and then one symmetric pair
    of scalar entries per unit of remaining excess, until exactly ``nnz``
    entries are left.  The diagonal is always kept.
    """
    nx, ny, nz = (int(g) for g in grid)
    d = int(dofs_per_node)
    n_nodes = nx * ny * nz
    if n_nodes * d != rows:
        raise ValueError(f"grid {grid} x {d} dofs gives {n_nodes * d} rows, "
                         f"not {rows}")
    x, y, z = np.meshgrid(np.arange(nx), np.arange(ny), np.arange(nz),
                          indexing="ij")
    x, y, z = x.ravel(), y.ravel(), z.ravel()
    node = x + nx * (y + ny * z)
    # one direction of each undirected pair: the 13 lexicographically
    # positive offsets of the 27-point stencil
    offsets = [(dx, dy, dz) for dz in (-1, 0, 1) for dy in (-1, 0, 1)
               for dx in (-1, 0, 1) if (dz, dy, dx) > (0, 0, 0)]
    src, dst = [], []
    for dx, dy, dz in offsets:
        ok = ((x + dx >= 0) & (x + dx < nx) & (y + dy >= 0) & (y + dy < ny)
              & (z + dz >= 0) & (z + dz < nz))
        src.append(node[ok])
        dst.append(node[ok] + dx + nx * (dy + ny * dz))
    src, dst = np.concatenate(src), np.concatenate(dst)

    diag_entries = d * d * n_nodes
    if (nnz - diag_entries) % 2:
        raise ValueError("a symmetric pattern with a full diagonal block "
                         f"cannot hold {nnz} entries")
    keep_pairs = -(-(nnz - diag_entries) // (2 * d * d))
    if not 0 <= keep_pairs <= src.shape[0]:
        raise ValueError(f"the mesh holds {src.shape[0]} node pairs; "
                         f"{keep_pairs} are needed")
    keep = np.sort(rng.choice(src.shape[0], keep_pairs, replace=False))
    nr = np.concatenate([src[keep], dst[keep], np.arange(n_nodes)])
    nc = np.concatenate([dst[keep], src[keep], np.arange(n_nodes)])
    # expand node blocks into dofs x dofs scalar blocks
    li, lj = np.meshgrid(np.arange(d), np.arange(d), indexing="ij")
    row = (nr[:, None] * d + li.ravel()[None, :]).ravel()
    col = (nc[:, None] * d + lj.ravel()[None, :]).ravel()

    excess = row.shape[0] - nnz            # even, see above
    upper = np.flatnonzero(row < col)
    drop = rng.choice(upper, excess // 2, replace=False)
    drop_key = np.concatenate([row[drop] * rows + col[drop],
                               col[drop] * rows + row[drop]])
    alive = ~np.isin(row * rows + col, drop_key)
    indptr, indices = _csr_from_pairs(rows, row[alive], col[alive])
    assert indices.shape[0] == nnz
    return indptr, indices


def banded_spd(rng: np.random.Generator, *, rows: int, nnz: int,
               half_bandwidth: int):
    """Symmetric banded pattern with a full diagonal: ``(nnz - rows) / 2``
    distinct positions drawn uniformly from the strict upper band
    ``0 < j - i <= half_bandwidth``, mirrored below the diagonal."""
    n, w = int(rows), int(half_bandwidth)
    if (nnz - n) % 2:
        raise ValueError(f"{nnz} entries with a full diagonal of {n} are "
                         "not symmetric")
    m = (nnz - n) // 2
    per_offset = n - np.arange(1, w + 1)          # band positions at j-i=k
    starts = np.concatenate([[0], np.cumsum(per_offset)])
    if m > starts[-1]:
        raise ValueError(f"the band holds {starts[-1]} upper positions; "
                         f"{m} are needed")
    pick = rng.choice(starts[-1], m, replace=False)
    k = np.searchsorted(starts, pick, side="right")   # offset 1..w
    i = pick - starts[k - 1]
    j = i + k
    diag = np.arange(n)
    indptr, indices = _csr_from_pairs(
        n, np.concatenate([i, j, diag]), np.concatenate([j, i, diag]))
    assert indices.shape[0] == nnz
    return indptr, indices


def value_rng(seed: int) -> np.random.Generator:
    """The generator of a run's values (any whole number seeds it)."""
    return np.random.default_rng([seed % 2**64, 1])


def normal_values(rng: np.random.Generator, nnz: int, dtype) -> np.ndarray:
    """Standard-normal values (never exactly zero in practice)."""
    return rng.standard_normal(nnz).astype(dtype)


def spd_values(rng: np.random.Generator, indptr: np.ndarray,
               indices: np.ndarray, dtype) -> np.ndarray:
    """Values on a symmetric pattern that make the matrix SPD.

    Off-diagonal values are standard normal and symmetric; each diagonal
    entry is 1 plus the sum of its row's off-diagonal magnitudes (strict
    diagonal dominance with a positive diagonal).
    """
    n = indptr.shape[0] - 1
    row = np.repeat(np.arange(n), np.diff(indptr))
    col = indices
    upper = row < col
    vals = np.zeros(indices.shape[0], np.float64)
    vals[upper] = rng.standard_normal(int(upper.sum()))
    # mirror: position of (j, i) for every stored (i, j)
    key = row * n + col
    order = np.argsort(key)                     # CSR keys are already sorted
    mirror = order[np.searchsorted(key[order], col * n + row)]
    lower = row > col
    vals[lower] = vals[mirror[lower]]
    diag = row == col
    rowsum = np.bincount(row, weights=np.abs(vals), minlength=n)
    vals[diag] = 1.0 + rowsum[row[diag]]
    return vals.astype(dtype)


def pattern_of(config: dict):
    """The configuration's pattern: one matrix, drawn from the file's own
    ``pattern_seed``, whatever the run's seed (which draws the values)."""
    rng = np.random.default_rng(int(config["pattern_seed"]))
    params = {k: config[k] for k in GENERATORS[config["generator"]][1]}
    return GENERATORS[config["generator"]][0](rng, **params)


GENERATORS = {
    "fem_node_mesh": (fem_node_mesh, ("rows", "nnz", "grid", "dofs_per_node")),
    "banded_spd": (banded_spd, ("rows", "nnz", "half_bandwidth")),
}
