"""The stand-in generators hit their published counts exactly, and the
benchmark's work counts match a brute-force count."""
from __future__ import annotations

import numpy as np
import pytest

from bench import generators as G
from bench.ops import cg as cg_op
from bench.ops import spgemm as spgemm_op
from bench.tests import tiny


def _dense(indptr, indices):
    n = indptr.shape[0] - 1
    d = np.zeros((n, n), bool)
    d[np.repeat(np.arange(n), np.diff(indptr)), indices] = True
    return d


@pytest.mark.parametrize("grid,dofs,nnz", [((3, 3, 4), 3, 4000),
                                           ((4, 3, 5), 2, 2000),
                                           ((2, 2, 2), 3, 370)])
def test_fem_node_mesh_exact_and_symmetric(grid, dofs, nnz):
    rows = int(np.prod(grid)) * dofs
    indptr, indices = G.fem_node_mesh(np.random.default_rng(3), rows=rows,
                                      nnz=nnz, grid=grid, dofs_per_node=dofs)
    assert indptr[-1] == indices.shape[0] == nnz
    d = _dense(indptr, indices)
    assert (d == d.T).all() and d.diagonal().all()
    # every row's columns are sorted and distinct
    for i in range(rows):
        row = indices[indptr[i]:indptr[i + 1]]
        assert (np.diff(row) > 0).all()
    # nonzeros lie within the node band of a lattice numbered x fastest
    nx, ny, _ = grid
    r, c = np.nonzero(d)
    assert np.max(np.abs(r // dofs - c // dofs)) <= nx * ny + nx + 1


def test_fem_node_mesh_refuses_impossible_counts():
    with pytest.raises(ValueError):
        G.fem_node_mesh(np.random.default_rng(0), rows=36, nnz=101,
                        grid=(2, 2, 3), dofs_per_node=3)
    with pytest.raises(ValueError):
        G.fem_node_mesh(np.random.default_rng(0), rows=37, nnz=100,
                        grid=(2, 2, 3), dofs_per_node=3)


@pytest.mark.parametrize("rows,nnz,w", [(300, 2300, 8), (50, 50, 3),
                                        (64, 64 + 2 * 100, 2)])
def test_banded_spd_exact_and_in_band(rows, nnz, w):
    if nnz - rows > 2 * sum(rows - k for k in range(1, w + 1)):
        with pytest.raises(ValueError):
            G.banded_spd(np.random.default_rng(5), rows=rows, nnz=nnz,
                         half_bandwidth=w)
        return
    indptr, indices = G.banded_spd(np.random.default_rng(5), rows=rows,
                                   nnz=nnz, half_bandwidth=w)
    assert indptr[-1] == indices.shape[0] == nnz
    d = _dense(indptr, indices)
    assert (d == d.T).all() and d.diagonal().all()
    r, c = np.nonzero(d)
    assert np.max(np.abs(r - c)) <= w


def test_spd_values_symmetric_dominant_and_fresh():
    indptr, indices = G.banded_spd(np.random.default_rng(1), rows=200,
                                   nnz=1400, half_bandwidth=6)
    rng = np.random.default_rng(2)
    v1 = G.spd_values(rng, indptr, indices, np.float64)
    v2 = G.spd_values(rng, indptr, indices, np.float64)
    assert not np.array_equal(v1, v2)
    n = 200
    a = np.zeros((n, n))
    a[np.repeat(np.arange(n), np.diff(indptr)), indices] = v1
    assert np.array_equal(a, a.T)
    off = np.abs(a).sum(1) - np.abs(a.diagonal())
    assert (a.diagonal() > off).all()
    np.linalg.cholesky(a)                      # SPD


def test_pattern_is_the_configurations_values_are_the_runs():
    config, traffic, _, _ = tiny.cell("cant.spgemm")
    p1 = G.pattern_of(config)
    p2 = G.pattern_of(dict(config, pattern_seed=1))
    assert p2[1].shape == p1[1].shape and not np.array_equal(p1[1], p2[1])
    s1 = spgemm_op.prepare(config, traffic, 7)
    s2 = spgemm_op.prepare(config, traffic, 7)
    s3 = spgemm_op.prepare(config, traffic, 8)
    assert np.array_equal(s1.indices, s3.indices)
    assert np.array_equal(s1.ring[0], s2.ring[0])
    assert not np.array_equal(s1.ring[0], s3.ring[0])


def test_spgemm_work_counts_match_brute_force():
    config, traffic, _, _ = tiny.cell("cant.spgemm")
    state = spgemm_op.prepare(config, traffic, tiny.SEED)
    ip, ix = state.indptr, state.indices
    products, out = 0, set()
    for i in range(state.n):
        for k in ix[ip[i]:ip[i + 1]]:
            products += ip[k + 1] - ip[k]
            out.update((i, j) for j in ix[ip[k]:ip[k + 1]])
    work = spgemm_op.work(state)
    assert work["flops"] == 2 * products
    nnz = ix.shape[0]
    assert work["bytes"] == (2 * nnz + len(out)) * 8 + 3 * (state.n + 1) * 4


def test_spmv_work_counts():
    config, traffic, _, _ = tiny.cell("bcsstk17.cg")
    state = cg_op.prepare(config, traffic, tiny.SEED)
    nnz = state.indices.shape[0]
    work = cg_op.work(state)
    assert work["flops"] == 2 * nnz
    assert work["bytes"] == nnz * 8 + (state.n + 1) * 4 + 2 * state.n * 4
