"""SpGEMM inspector-executor: correctness vs dense oracle, both paths."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import (CSR, choose_spgemm_path, inspect_spgemm_block,
                        inspect_spgemm_gather, random_csr, spgemm,
                        spgemm_gather_execute_chunk, spgemm_ref_numpy)
from repro.core.spgemm import block_result_to_dense
from repro.runtime import ReapRuntime
from repro.runtime.pipeline import block_chunk_tiles, build_block_chunkset


def _rand(n, m, density, seed=0, pattern="uniform"):
    return random_csr(n, m, density, np.random.default_rng(seed), pattern)


def _dense_oracle(a: CSR, b: CSR):
    return a.to_dense().astype(np.float64) @ b.to_dense().astype(np.float64)


def _with_values(a: CSR, data) -> CSR:
    return CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
               np.asarray(data, np.float32))


def _block_tiles(plan, a_data, b_data) -> np.ndarray:
    """A block plan's output tiles from a one-chunk chunk set, through the
    pipeline's emit and execute stages; builds the plan's extraction index
    the way the pipeline does."""
    chunkset = build_block_chunkset(plan, 1)

    def tiles(a_vals, b_vals):
        return block_chunk_tiles(chunkset, a_vals, b_vals, use_pallas=False)
    plan.out_csr_index(tiles)
    return tiles(a_data, b_data)


def _case_banded():
    return (_rand(90, 70, 0.07, 21, "banded"),
            _rand(70, 50, 0.07, 22, "banded"), 16, False)


def _case_blocky():
    return (_rand(128, 96, 0.08, 30, "blocky"),
            _rand(96, 64, 0.08, 31, "blocky"), 32, False)


def _case_ragged_dims():
    # no dimension a multiple of the block: padded tile rows/cols stay out
    return _rand(45, 37, 0.1, 32), _rand(37, 29, 0.1, 33), 16, False


def _case_empty_product():
    a = CSR.from_dense(np.zeros((40, 30), np.float32))
    return a, _rand(30, 20, 0.2, 34), 16, False


def _case_cancellation():
    # C[0, 0] = 1·1 + 1·(−1) = 0 exactly, inside the structural pattern
    dense_a = _rand(40, 40, 0.1, 35).to_dense()
    dense_a[0] = 0.0
    dense_a[0, :2] = 1.0
    dense_b = _rand(40, 40, 0.1, 36).to_dense()
    dense_b[:2, 0] = (1.0, -1.0)
    return CSR.from_dense(dense_a), CSR.from_dense(dense_b), 16, True


def _case_stored_zeros():
    # explicitly stored zeros in A: their products are structural zeros
    a = _rand(60, 50, 0.1, 37)
    data = a.data.copy()
    data[::3] = 0.0
    return _with_values(a, data), _rand(50, 40, 0.1, 38), 16, True


_EXTRACTION_CASES = {
    "banded": _case_banded, "blocky": _case_blocky,
    "ragged_dims": _case_ragged_dims, "empty_product": _case_empty_product,
    "cancellation": _case_cancellation, "stored_zeros": _case_stored_zeros}


class TestGatherPath:
    @given(st.integers(5, 120), st.integers(5, 120), st.integers(5, 120),
           st.floats(0.01, 0.3), st.integers(0, 5))
    @settings(max_examples=25, deadline=None)
    def test_matches_dense(self, n, k, m, density, seed):
        a, b = _rand(n, k, density, seed), _rand(k, m, density, seed + 100)
        plan = inspect_spgemm_gather(a, b)
        c_data = spgemm_gather_execute_chunk(plan, a.data, b.data)
        c = CSR(n, m, plan.c_indptr, plan.c_indices, c_data)
        np.testing.assert_allclose(c.to_dense(), _dense_oracle(a, b),
                                   rtol=1e-4, atol=1e-5)

    def test_empty_result(self):
        a = CSR.from_dense(np.zeros((4, 4), np.float32))
        b = _rand(4, 4, 0.5)
        plan = inspect_spgemm_gather(a, b)
        assert plan.c_nnz == 0
        c_data = spgemm_gather_execute_chunk(plan, a.data, b.data)
        assert c_data.shape == (0,)

    def test_plan_partials_sorted(self):
        a, b = _rand(50, 50, 0.1, 1), _rand(50, 50, 0.1, 2)
        plan = inspect_spgemm_gather(a, b)
        assert (np.diff(plan.out_idx) >= 0).all()  # host did the sort unit's job

    def test_padding_dead_slots(self):
        a, b = _rand(30, 30, 0.05, 3), _rand(30, 30, 0.05, 4)
        plan = inspect_spgemm_gather(a, b, tile=1024)
        assert plan.a_idx.shape[0] % 1024 == 0
        assert (plan.out_idx[plan.n_pp:] == plan.c_nnz).all()


class TestBlockPath:
    @pytest.mark.parametrize("block", [8, 32])
    @pytest.mark.parametrize("pattern", ["uniform", "blocky", "banded"])
    def test_matches_dense(self, block, pattern):
        a = _rand(100, 80, 0.08, 7, pattern)
        b = _rand(80, 60, 0.08, 8, pattern)
        plan = inspect_spgemm_block(a, b, block)
        c_blocks = _block_tiles(plan, a.data, b.data)
        dense = block_result_to_dense(plan, c_blocks)
        np.testing.assert_allclose(dense[:100, :60], _dense_oracle(a, b),
                                   rtol=1e-4, atol=1e-4)

    def test_schedule_group_flags(self):
        a, b = _rand(64, 64, 0.1, 9), _rand(64, 64, 0.1, 10)
        plan = inspect_spgemm_block(a, b, 16)
        assert plan.is_first.sum() == plan.n_out_blocks
        assert plan.is_last.sum() == plan.n_out_blocks
        # within a group the out_id is constant and groups are contiguous
        starts = np.nonzero(plan.is_first)[0]
        ends = np.nonzero(plan.is_last)[0]
        for s, e in zip(starts, ends):
            assert (plan.out_id[s:e + 1] == plan.out_id[s]).all()


class TestPublicAPI:
    def test_ref_matches_dense(self):
        a, b = _rand(60, 70, 0.1, 11), _rand(70, 50, 0.1, 12)
        c = spgemm_ref_numpy(a, b)
        np.testing.assert_allclose(c.to_dense(), _dense_oracle(a, b),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("method", ["gather", "block"])
    def test_spgemm_api(self, method):
        a = _rand(70, 70, 0.08, 13, "blocky")
        c, stats = spgemm(a, a, method=method, block=32, use_pallas=False)
        np.testing.assert_allclose(c.to_dense(), _dense_oracle(a, a),
                                   rtol=1e-4, atol=1e-4)
        assert stats["inspect_s"] > 0 and stats["execute_s"] > 0

    def test_path_heuristic(self):
        sparse = _rand(512, 512, 0.001, 14)
        densish = CSR.from_dense(np.ones((128, 128), np.float32))
        assert choose_spgemm_path(sparse, sparse) == "gather"
        assert choose_spgemm_path(densish, densish) == "block"

    def test_a_squared_paper_protocol(self):
        # the paper evaluates C = A^2
        a = _rand(90, 90, 0.05, 15, "powerlaw")
        c, _ = spgemm(a, a, method="gather")
        np.testing.assert_allclose(c.to_dense(), _dense_oracle(a, a),
                                   rtol=1e-4, atol=1e-5)


class TestPlannedExecution:
    """A cold call, then a warm call with fresh values, of the runtime's
    planned path at one chunk: the plan cache replays the plan."""

    def test_gather_plan_reuse(self):
        a, b = _rand(80, 80, 0.08, 16), _rand(80, 80, 0.08, 17)
        rt = ReapRuntime(n_chunks=1)
        c_plain, _ = spgemm(a, b, method="gather")
        c_cold, cold = rt.run("spgemm", a, b, method="gather")
        assert cold["method"] == "gather_chunked" and not cold["cache_hit"]
        np.testing.assert_array_equal(c_cold.to_dense(), c_plain.to_dense())
        # same plan, fresh values (the cache-hit workload)
        rng = np.random.default_rng(18)
        a2 = CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
                 rng.standard_normal(a.nnz).astype(np.float32))
        c2, warm = rt.run("spgemm", a2, b, method="gather")
        assert warm["cache_hit"] and warm["plan_s"] == 0.0
        np.testing.assert_allclose(c2.to_dense(), _dense_oracle(a2, b),
                                   rtol=1e-4, atol=1e-5)

    def test_block_plan_reuse(self):
        a = _rand(96, 96, 0.08, 19, "blocky")
        rt = ReapRuntime(n_chunks=1, block=32, use_pallas=False)
        c_plain, _ = spgemm(a, a, method="block", block=32, use_pallas=False)
        c_cold, cold = rt.run("spgemm", a, a, method="block")
        assert cold["method"] == "block_chunked" and not cold["cache_hit"]
        np.testing.assert_array_equal(c_cold.to_dense(), c_plain.to_dense())
        a2 = _with_values(a, np.random.default_rng(20).standard_normal(a.nnz))
        c2, warm = rt.run("spgemm", a2, a2, method="block")
        assert warm["cache_hit"] and warm["plan_s"] == 0.0
        np.testing.assert_allclose(c2.to_dense(), _dense_oracle(a2, a2),
                                   rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("case", sorted(_EXTRACTION_CASES))
    def test_block_csr_extraction_matches_dense_roundtrip(self, case):
        from repro.core import block_result_to_csr
        from repro.runtime import spans
        a, b, block, must_drop = _EXTRACTION_CASES[case]()
        plan = inspect_spgemm_block(a, b, block)
        c_blocks = _block_tiles(plan, a.data, b.data)
        via_dense = CSR.from_dense(
            block_result_to_dense(plan, c_blocks)[:a.n_rows, :b.n_cols])
        with spans.record("reap.run") as rec:
            direct = block_result_to_csr(plan, c_blocks, a.n_rows, b.n_cols)
        for name in ("indptr", "indices", "data"):
            got, want = getattr(direct, name), getattr(via_dense, name)
            assert got.dtype == want.dtype, name
            np.testing.assert_array_equal(got, want)
        # exact zeros inside A·B's structural pattern are dropped and counted
        n_structural = plan.out_csr_index(None).sel.size
        dropped = rec.counters["extract_zeros_dropped"]
        assert dropped == n_structural - direct.nnz
        assert (dropped > 0) == must_drop

    def test_block_extraction_index_is_pattern_pure(self):
        from repro.core import block_result_to_csr
        a, b = _rand(96, 80, 0.08, 23, "blocky"), _rand(80, 64, 0.08, 24)
        plan = inspect_spgemm_block(a, b, 32)
        outs = []
        for seed in (25, 26):
            vals = np.random.default_rng(seed).standard_normal
            a2 = CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
                     vals(a.nnz).astype(np.float32))
            b2 = CSR(b.n_rows, b.n_cols, b.indptr, b.indices,
                     vals(b.nnz).astype(np.float32))
            c_blocks = _block_tiles(plan, a2.data, b2.data)
            outs.append(block_result_to_csr(plan, c_blocks, 96, 64))
            np.testing.assert_allclose(outs[-1].to_dense(),
                                       _dense_oracle(a2, b2),
                                       rtol=1e-4, atol=1e-4)
        index = plan.out_csr_index(None)     # memoized: no executor needed
        assert not np.array_equal(outs[0].data, outs[1].data)
        np.testing.assert_array_equal(outs[0].indices, outs[1].indices)
        for c in outs:
            assert c.indices is index.indices and c.indptr is index.indptr

    def test_block_extraction_after_plan_store_round_trip(self, tmp_path):
        from repro.core import block_result_to_csr
        from repro.core.inspector import fingerprint_pattern
        from repro.runtime import PlanStore
        a, b = _rand(70, 90, 0.08, 27, "banded"), _rand(90, 60, 0.08, 28)
        plan = inspect_spgemm_block(a, b, 16)
        c_blocks = _block_tiles(plan, a.data, b.data)
        before = block_result_to_csr(plan, c_blocks, 70, 60)
        fp = fingerprint_pattern("spgemm_block", (a, b), block=16)
        PlanStore(tmp_path).put(fp, plan)
        back = PlanStore(tmp_path).get(fp)
        assert getattr(back, "_out_csr_index", None) is None   # not stored
        with pytest.raises(ValueError, match="not built"):
            block_result_to_csr(back, c_blocks, 70, 60)
        np.testing.assert_array_equal(_block_tiles(back, a.data, b.data),
                                      c_blocks)
        after = block_result_to_csr(back, c_blocks, 70, 60)
        for name in ("indptr", "indices", "data"):
            np.testing.assert_array_equal(getattr(after, name),
                                          getattr(before, name))
        for got, want in zip(back.out_csr_index(None),
                             plan.out_csr_index(None)):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(got, want)

    def test_block_extraction_shares_read_only_structure(self):
        from repro.core import block_result_to_csr
        a = _rand(64, 64, 0.1, 29, "blocky")
        plan = inspect_spgemm_block(a, a, 16)
        c = block_result_to_csr(
            plan, _block_tiles(plan, a.data, a.data), 64, 64)
        for arr in (c.indptr, c.indices):
            assert not arr.flags.writeable
            with pytest.raises(ValueError):
                arr[0] = 1
        c.data[0] = 1.0                      # a product's own values


def _gather_plan(seed, pattern="uniform"):
    a, b = _rand(70, 60, 0.1, seed, pattern), _rand(60, 50, 0.1, seed + 1)
    return a, b, inspect_spgemm_gather(a, b, tile=64)


def _fresh_values(m: CSR, seed) -> CSR:
    return _with_values(m, np.random.default_rng(seed).standard_normal(m.nnz))


class TestGatherDeviceIndices:
    """The chunked gather executor's per-plan memo of the padded index
    arrays on the device (``SpGemmGatherPlan.device_indices``)."""

    @pytest.mark.parametrize("pattern", ["uniform", "banded", "powerlaw"])
    def test_products_on_one_plan_match_reference_and_share_the_memo(
            self, pattern):
        from repro.runtime import spans
        a, b, plan = _gather_plan(41, pattern)
        memos, builds = [], []
        for seed in (42, 43):
            a2, b2 = _fresh_values(a, seed), _fresh_values(b, seed + 10)
            with spans.record("reap.run") as rec:
                c = spgemm_gather_execute_chunk(plan, a2.data, b2.data)
            builds.append(rec.counters["gather_index_builds"])
            ref = spgemm_ref_numpy(a2, b2)
            np.testing.assert_array_equal(plan.c_indices, ref.indices)
            np.testing.assert_allclose(c, ref.data, rtol=1e-5, atol=1e-5)
            memos.append(plan.device_indices(a.nnz, b.nnz, put=None))
        assert builds == [1, 0]
        assert memos[0] is memos[1]
        assert memos[0].cap >= plan.a_idx.shape[0] and memos[0].c_cap >= \
            plan.c_nnz
        for first, second in zip(memos[0][:3], memos[1][:3]):
            assert second is first

    @pytest.mark.parametrize("via", ["serialize", "plan_store"])
    def test_round_trip_carries_no_memo_and_rebuilds_it(self, via,
                                                        tmp_path):
        from repro.core.inspector import fingerprint_pattern
        from repro.runtime import PlanStore
        from repro.runtime.plan_cache import (_entry_nbytes,
                                              deserialize_plan,
                                              serialize_plan)
        a, b, plan = _gather_plan(44)
        nbytes = _entry_nbytes(plan)
        before = spgemm_gather_execute_chunk(plan, a.data, b.data)
        assert _entry_nbytes(plan) == nbytes      # the memo is not counted
        if via == "serialize":
            back = deserialize_plan(serialize_plan(plan))
        else:
            fp = fingerprint_pattern("spgemm_gather", (a, b), tile=64)
            PlanStore(tmp_path).put(fp, plan)
            back = PlanStore(tmp_path).get(fp)
        assert getattr(back, "_device_indices", None) is None  # not stored
        after = spgemm_gather_execute_chunk(back, a.data, b.data)
        np.testing.assert_array_equal(after, before)
        rebuilt = back.device_indices(a.nnz, b.nnz, put=None)
        original = plan.device_indices(a.nnz, b.nnz, put=None)
        assert rebuilt is not original
        assert rebuilt[3:] == original[3:]
        for got, want in zip(rebuilt[:3], original[:3]):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("operand", ["a", "b"])
    def test_other_value_lengths_raise(self, operand):
        a, b, plan = _gather_plan(45)
        spgemm_gather_execute_chunk(plan, a.data, b.data)
        a_data, b_data = a.data, b.data
        if operand == "a":
            a_data = np.append(a_data, np.float32(0))
        else:
            b_data = b_data[:-1]
        with pytest.raises(ValueError, match="device indices"):
            spgemm_gather_execute_chunk(plan, a_data, b_data)


class TestChunkedGatherOnMesh:
    """The runtime's default path on a shrunk ``bench/meshes.py`` pattern
    (the ``cop20K`` stand-in's generator): ``auto`` → chunked gather."""

    def test_matches_reference(self):
        import json
        from pathlib import Path

        from bench import meshes
        from repro.runtime import ReapRuntime
        path = Path(__file__).resolve().parents[1] / "bench" / "configs"
        config = json.loads((path / "cop20K.json").read_text())
        config.update(rows=3000, nnz=64961)
        indptr, indices = meshes.pattern_of(config)
        n = config["rows"]
        a = CSR(n, n, indptr, indices, np.random.default_rng(40)
                .standard_normal(indices.shape[0]).astype(np.float32))
        c, stats = ReapRuntime().run("spgemm", a, a)
        assert stats["method"] == "gather_chunked" and stats["n_chunks"] > 1
        ref = spgemm_ref_numpy(a, a)
        np.testing.assert_array_equal(c.indptr, ref.indptr)
        np.testing.assert_array_equal(c.indices, ref.indices)
        np.testing.assert_allclose(c.data, ref.data, rtol=1e-5, atol=1e-5)
