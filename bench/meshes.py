"""Seeded stand-in for an unstructured finite-element mesh matrix.

:func:`knn_mesh` returns a symmetric sparsity pattern ``(indptr, indices)``
(CSR, int64, columns sorted within a row) with exactly the rows and
nonzeros its configuration states, as ``bench.generators`` does for the
lattice and band stand-ins.  :func:`pattern_of` draws a configuration's one
pattern from its ``pattern_seed``; values come from the run's seed
(``bench.generators.normal_values``).
"""
from __future__ import annotations

import numpy as np
from scipy.spatial import cKDTree

from bench.generators import _csr_from_pairs

PARAMS = ("rows", "nnz", "regions", "median_neighbours", "region_sigma",
          "node_sigma", "min_neighbours", "max_neighbours")


def knn_mesh(rng: np.random.Generator, *, rows: int, nnz: int,
             regions: int, median_neighbours: float, region_sigma: float,
             node_sigma: float, min_neighbours: int, max_neighbours: int):
    """Pattern of an unstructured 3-D mesh with uneven rows.

    ``rows`` nodes sit at uniform random points of the unit cube.  The cube
    is cut into ``regions³`` equal boxes, and each box draws a median
    neighbour count from a lognormal law (median ``median_neighbours``,
    log-sd ``region_sigma``), as refinement and element types vary from one
    part of a mesh to another; each node draws its own count about its
    box's median (log-sd ``node_sigma``), rounded and clipped to
    ``[min_neighbours, max_neighbours]``.  Each node couples to that many
    nearest nodes, and the union of the couplings is the symmetric
    off-diagonal pattern.  Coupled pairs are dropped at random until
    exactly ``nnz`` entries are left.  The diagonal is whole when
    ``nnz - rows`` is even, and otherwise lacks one node, drawn at random.
    Nodes are numbered in a random order, as a mesh is that no reordering
    has banded.
    """
    n = int(rows)
    pts = rng.random((n, 3))
    box = np.minimum((pts * regions).astype(np.int64), regions - 1)
    box = box[:, 0] + regions * (box[:, 1] + regions * box[:, 2])
    box_median = median_neighbours * np.exp(
        rng.normal(0.0, region_sigma, regions ** 3))
    k = box_median[box] * np.exp(rng.normal(0.0, node_sigma, n))
    k_max = min(int(max_neighbours), n - 1)
    k = np.clip(np.rint(k), min_neighbours, k_max).astype(np.int64)

    _, near = cKDTree(pts).query(pts, k=int(k.max()) + 1, workers=-1)
    near = near[:, 1:]                          # column 0 is the node itself
    src = np.repeat(np.arange(n, dtype=np.int64), k)
    dst = near[np.arange(near.shape[1])[None, :] < k[:, None]]
    pairs = np.unique(np.minimum(src, dst) * n + np.maximum(src, dst))

    n_diag = n - (nnz - n) % 2
    n_pairs = (nnz - n_diag) // 2
    if not 0 <= n_pairs <= pairs.shape[0]:
        raise ValueError(f"the couplings give {pairs.shape[0]} node pairs; "
                         f"{n_pairs} are needed")
    pairs = rng.choice(pairs, n_pairs, replace=False)
    diag = np.delete(np.arange(n), rng.integers(n)) if n_diag < n \
        else np.arange(n)
    number = rng.permutation(n)
    lo, hi = number[pairs // n], number[pairs % n]
    diag = number[diag]
    indptr, indices = _csr_from_pairs(n, np.concatenate([lo, hi, diag]),
                                      np.concatenate([hi, lo, diag]))
    assert indices.shape[0] == nnz
    return indptr, indices


def pattern_of(config: dict):
    """The configuration's pattern, drawn from its own ``pattern_seed``
    whatever the run's seed."""
    rng = np.random.default_rng(int(config["pattern_seed"]))
    return knn_mesh(rng, **{key: config[key] for key in PARAMS})
