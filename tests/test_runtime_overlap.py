"""Pipeline correctness: chunked/double-buffered execution must match the
numpy references (to tolerance) at one chunk and at several, across
pattern families.

Families: banded, random (uniform), power-law, block-diagonal, empty rows.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import (CSR, COO, cholesky_baseline_numpy, cholesky_values,
                        inspect_cholesky, inspect_spgemm_block,
                        plan_to_dense_l, random_csr, random_spd_csr,
                        spgemm_ref_numpy)
from repro.runtime import (ReapRuntime, bucket_block_schedule,
                           build_block_chunkset, cholesky_execute_overlapped,
                           chunk_row_bounds, run_overlapped,
                           spgemm_block_chunked, spgemm_gather_chunked)


def _family(name: str, n: int, m: int, density: float, seed: int) -> CSR:
    rng = np.random.default_rng(seed)
    if name == "empty_rows":
        a = random_csr(n, m, density, rng, "uniform")
        coo = a.to_coo()
        dead = rng.choice(n, size=n // 3, replace=False)   # kill 1/3 of rows
        keep = ~np.isin(coo.row, dead)
        return CSR.from_coo(COO(n, m, coo.row[keep], coo.col[keep],
                                coo.val[keep]))
    pattern = {"banded": "banded", "random": "uniform",
               "powerlaw": "powerlaw", "blockdiag": "blocky"}[name]
    return random_csr(n, m, density, rng, pattern)


FAMILIES = ["banded", "random", "powerlaw", "blockdiag", "empty_rows"]


class TestRunOverlapped:
    def test_matches_sync_and_order(self):
        log = []

        def inspect_fn(k):
            return k * 10

        def execute_fn(k, art):
            log.append((k, art))
            return art + 1

        res_one, st_one = run_overlapped(1, inspect_fn, execute_fn)
        assert res_one == [1] and log == [(0, 0)]
        log[:] = []
        res_over, st_over = run_overlapped(5, inspect_fn, execute_fn)
        assert res_over == [1, 11, 21, 31, 41]
        # execution order preserved
        assert log == [(k, k * 10) for k in range(5)]
        assert not st_one.overlap and st_over.overlap

    def test_zero_chunks(self):
        res, st = run_overlapped(0, lambda k: k, lambda k, a: a)
        assert res == [] and st.n_chunks == 0


class TestChunkBounds:
    @pytest.mark.parametrize("family", FAMILIES)
    def test_bounds_cover_rows(self, family):
        a = _family(family, 97, 83, 0.05, 3)
        bounds = chunk_row_bounds(a, 4)
        assert bounds[0] == 0 and bounds[-1] == a.n_rows
        assert (np.diff(bounds) > 0).all()

    def test_empty_matrix(self):
        a = CSR.from_dense(np.zeros((5, 5), np.float32))
        bounds = chunk_row_bounds(a, 4)
        assert bounds[0] == 0 and bounds[-1] == 5


class TestChunkedSpgemm:
    @pytest.mark.parametrize("family", FAMILIES)
    @pytest.mark.parametrize("n_chunks", [1, 4])
    def test_matches_reference(self, family, n_chunks):
        a = _family(family, 120, 110, 0.05, 11)
        b = _family(family, 110, 90, 0.05, 12)
        c, stats, _ = spgemm_gather_chunked(a, b, n_chunks=n_chunks)
        ref = spgemm_ref_numpy(a, b)
        np.testing.assert_allclose(c.to_dense().astype(np.float64),
                                   ref.to_dense().astype(np.float64),
                                   rtol=1e-4, atol=1e-5)
        assert stats["overlap"] == (stats["n_chunks"] > 1)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_warm_chunkset_matches(self, family):
        a = _family(family, 100, 100, 0.06, 13)
        b = _family(family, 100, 100, 0.06, 14)
        _, _, chunkset = spgemm_gather_chunked(a, b, n_chunks=3)
        # same pattern, new values, warm chunk set
        rng = np.random.default_rng(15)
        a2 = CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
                 rng.standard_normal(a.nnz).astype(np.float32))
        c, stats, _ = spgemm_gather_chunked(a2, b, n_chunks=3,
                                            chunkset=chunkset)
        np.testing.assert_allclose(c.to_dense().astype(np.float64),
                                   spgemm_ref_numpy(a2, b).to_dense(),
                                   rtol=1e-4, atol=1e-5)
        assert stats["inspect_s"] < 0.05   # warm: list lookups, no plan-build

    def test_single_chunk_degenerates(self):
        a = _family("random", 60, 60, 0.08, 16)
        c, stats, _ = spgemm_gather_chunked(a, a, n_chunks=1)
        assert stats["n_chunks"] == 1 and not stats["overlap"]
        np.testing.assert_allclose(c.to_dense().astype(np.float64),
                                   spgemm_ref_numpy(a, a).to_dense(),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_runtime_end_to_end(self, family):
        rt = ReapRuntime(n_chunks=4, use_pallas=False)
        a = _family(family, 90, 90, 0.06, 17)
        c, stats = rt.spgemm(a, a, method="gather")
        np.testing.assert_allclose(c.to_dense().astype(np.float64),
                                   spgemm_ref_numpy(a, a).to_dense(),
                                   rtol=1e-4, atol=1e-5)


class TestChunkedBlockSpgemm:
    """Block/MXU pipeline: schedule-group chunks must match the numpy
    reference across the pattern families."""

    @pytest.mark.parametrize("family", FAMILIES)
    @pytest.mark.parametrize("n_chunks", [1, 4])
    def test_matches_reference(self, family, n_chunks):
        a = _family(family, 120, 110, 0.05, 31)
        b = _family(family, 110, 90, 0.05, 32)
        c, stats, _ = spgemm_block_chunked(a, b, block=16, n_chunks=n_chunks,
                                           use_pallas=False)
        ref = spgemm_ref_numpy(a, b)
        np.testing.assert_allclose(c.to_dense().astype(np.float64),
                                   ref.to_dense().astype(np.float64),
                                   rtol=1e-3, atol=1e-3)
        assert stats["overlap"] == (stats["n_chunks"] > 1)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_warm_chunkset_matches(self, family):
        a = _family(family, 100, 100, 0.06, 33)
        b = _family(family, 100, 100, 0.06, 34)
        _, _, chunkset = spgemm_block_chunked(a, b, block=16, n_chunks=3,
                                              use_pallas=False)
        rng = np.random.default_rng(35)
        a2 = CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
                 rng.standard_normal(a.nnz).astype(np.float32))
        c, stats, out_set = spgemm_block_chunked(a2, b, block=16, n_chunks=3,
                                                 use_pallas=False,
                                                 chunkset=chunkset)
        np.testing.assert_allclose(c.to_dense().astype(np.float64),
                                   spgemm_ref_numpy(a2, b).to_dense(),
                                   rtol=1e-3, atol=1e-3)
        # warm: the passed-in chunk set (and its plan) is reused, not rebuilt
        assert out_set is chunkset and out_set.plan is chunkset.plan

    def test_chunks_align_to_schedule_groups(self):
        a = _family("blockdiag", 96, 96, 0.08, 36)
        plan = inspect_spgemm_block(a, a, 16)
        chunkset = build_block_chunkset(plan, 4)
        # every chunk starts at a group start and output blocks are whole
        assert chunkset.out_bounds[0] == 0
        assert chunkset.out_bounds[-1] == plan.n_out_blocks
        for ch in chunkset.chunks:
            assert ch.is_first[0] and ch.is_last[-1]
            assert ch.out_id[0] == 0
            assert ch.n_out_blocks == int(ch.out_id[-1]) + 1

    def test_single_chunk_degenerates(self):
        a = _family("blockdiag", 64, 64, 0.08, 37)
        c, stats, _ = spgemm_block_chunked(a, a, block=16, n_chunks=1,
                                           use_pallas=False)
        assert stats["n_chunks"] == 1 and not stats["overlap"]
        np.testing.assert_allclose(c.to_dense().astype(np.float64),
                                   spgemm_ref_numpy(a, a).to_dense(),
                                   rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("family", FAMILIES)
    def test_runtime_end_to_end(self, family):
        rt = ReapRuntime(n_chunks=3, block=16, use_pallas=False)
        a = _family(family, 90, 90, 0.06, 38)
        c, stats = rt.spgemm(a, a, method="block")
        assert stats["method"] == "block_chunked"
        np.testing.assert_allclose(c.to_dense().astype(np.float64),
                                   spgemm_ref_numpy(a, a).to_dense(),
                                   rtol=1e-3, atol=1e-3)
        _, stats2 = rt.spgemm(a, a, method="block")
        assert not stats["cache_hit"] and stats2["cache_hit"]


class TestBlockChunkBucketing:
    """Pow-2 shape bucketing of block-chunk executor operands: dead slots
    must not change results, and distinct compiled shapes must collapse to
    distinct bucket tuples (O(log), not one per raw chunk shape)."""

    def test_bucketed_schedule_shape_and_flags(self):
        a = _family("random", 100, 100, 0.06, 41)
        plan = inspect_spgemm_block(a, a, 16)
        chunkset = build_block_chunkset(plan, 3)
        from repro.core.inspector import next_pow2
        for k in range(chunkset.n_chunks):
            ch = chunkset.chunk(k)
            sched = bucket_block_schedule(ch)
            cap = next_pow2(max(1, ch.n_pairs))
            assert sched["pair_cap"] == cap
            for key in ("a_id", "b_id", "out_id", "is_first", "is_last"):
                assert sched[key].shape == (cap,)
            assert sched["a_cap"] >= ch.n_a_blocks
            assert sched["b_cap"] >= ch.n_b_blocks
            assert sched["out_cap"] >= ch.n_out_blocks
            # live prefix is untouched
            np.testing.assert_array_equal(sched["out_id"][:ch.n_pairs],
                                          ch.out_id)
            pad = cap - ch.n_pairs
            if pad:
                # dead slots: one trailing group aimed at the dummy tile
                tail = sched["out_id"][ch.n_pairs:]
                assert (tail == sched["out_cap"]).all()
                assert sched["is_first"][ch.n_pairs] == 1
                assert sched["is_last"][-1] == 1
                assert sched["is_first"][ch.n_pairs:].sum() == 1
                assert sched["is_last"][ch.n_pairs:].sum() == 1
            # memoized: second call returns the identical dict
            assert bucket_block_schedule(ch) is sched

    @pytest.mark.parametrize("family", FAMILIES)
    def test_bucketed_execution_matches_reference(self, family):
        # n chosen so chunk shapes are never powers of two already
        a = _family(family, 118, 107, 0.06, 42)
        b = _family(family, 107, 93, 0.06, 43)
        c, _, _ = spgemm_block_chunked(a, b, block=16, n_chunks=3,
                                       use_pallas=False)
        np.testing.assert_allclose(c.to_dense().astype(np.float64),
                                   spgemm_ref_numpy(a, b).to_dense(),
                                   rtol=1e-3, atol=1e-3)

    def test_mixed_patterns_bounded_compiles(self):
        """Across mixed sizes the executor compiles at most one shape per
        distinct bucket tuple."""
        from repro.core.spgemm import _block_execute_jnp
        rt = ReapRuntime(n_chunks=3, block=8, use_pallas=False)
        mats = [_family("blockdiag", n, n, 0.1, 60 + n)
                for n in (72, 80, 88, 96, 104)]
        before = _block_execute_jnp._cache_size()
        for m in mats:
            c, _ = rt.spgemm(m, m, method="block")
            np.testing.assert_allclose(c.to_dense().astype(np.float64),
                                       spgemm_ref_numpy(m, m).to_dense(),
                                       rtol=1e-3, atol=1e-3)
        compiles = _block_execute_jnp._cache_size() - before
        buckets, raw, chunks = set(), set(), 0
        for plan in rt.cache._entries.values():
            for k in range(plan.n_chunks):
                ch = plan.chunk(k)
                sched = bucket_block_schedule(ch)
                buckets.add((sched["pair_cap"], sched["a_cap"],
                             sched["b_cap"], sched["out_cap"]))
                raw.add((ch.n_pairs, ch.n_a_blocks, ch.n_b_blocks,
                         ch.n_out_blocks))
                chunks += 1
        assert compiles <= len(buckets) <= len(raw) <= chunks
        assert len(buckets) < chunks        # bucketing actually collapsed


def _spd_family(name: str, n: int, seed: int) -> CSR:
    rng = np.random.default_rng(seed)
    if name == "empty_rows":
        # structurally minimal rows: diagonal + one sub-block of couplings
        d = np.diag(rng.uniform(2.0, 3.0, n))
        k = n // 4
        blk = rng.standard_normal((k, k)) * 0.1
        d[:k, :k] += blk @ blk.T
        return CSR.from_dense(d)
    pattern = {"banded": "banded", "random": "uniform",
               "powerlaw": "powerlaw", "blockdiag": "blocky"}[name]
    return random_spd_csr(n, 0.06, rng, pattern)


class TestOverlappedCholesky:
    @pytest.mark.parametrize("family", FAMILIES)
    def test_matches_sync_and_numpy(self, family):
        a = _spd_family(family, 70, 21)
        plan = inspect_cholesky(a)
        a_vals = cholesky_values(a)
        base_vals, _ = cholesky_baseline_numpy(plan, a_vals)
        over_vals, stats = cholesky_execute_overlapped(plan, a_vals,
                                                       jnp.float64)
        np.testing.assert_allclose(over_vals, base_vals, rtol=1e-12,
                                   atol=1e-13)
        l = plan_to_dense_l(plan, over_vals)
        np.testing.assert_allclose(l, np.linalg.cholesky(a.to_dense()),
                                   rtol=1e-8, atol=1e-10)
        assert stats["n_levels"] == plan.n_levels

    @pytest.mark.parametrize("family", ["banded", "blockdiag"])
    def test_runtime_cholesky_overlap(self, family):
        rt = ReapRuntime(use_pallas=False)
        a = _spd_family(family, 60, 23)
        plan, vals, stats = rt.cholesky(a)
        l = plan_to_dense_l(plan, vals)
        np.testing.assert_allclose(l @ l.T, a.to_dense(), rtol=1e-8,
                                   atol=1e-9)
