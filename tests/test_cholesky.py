"""Sparse Cholesky: symbolic analysis + level-scheduled numeric executor."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax.numpy as jnp

from repro.core import (cholesky, cholesky_baseline_numpy, cholesky_values,
                        etree, etree_levels, inspect_cholesky, random_spd_csr,
                        plan_to_dense_l)
from repro.core.formats import CSR
from repro.runtime import ReapRuntime, spans


def _spd(n, density, seed, pattern="banded"):
    return random_spd_csr(n, density, np.random.default_rng(seed), pattern)


class TestEtree:
    def test_etree_known_arrowhead(self):
        # arrowhead matrix: every column's parent is n-1
        n = 6
        d = np.eye(n) * 10
        d[-1, :] = 1.0
        d[:, -1] = 1.0
        d[-1, -1] = 10
        a = CSR.from_dense(d)
        parent = etree(a.lower_triangle())
        assert list(parent[:-1]) == [n - 1] * (n - 1)
        assert parent[-1] == -1

    def test_tridiag_is_path(self):
        n = 8
        d = np.eye(n) * 4 + np.eye(n, k=1) + np.eye(n, k=-1)
        parent = etree(CSR.from_dense(d).lower_triangle())
        assert list(parent) == list(range(1, n)) + [-1]
        levels = etree_levels(parent)
        assert list(levels) == list(range(n))  # a path: no parallelism

    def test_diag_only_all_parallel(self):
        a = CSR.from_dense(np.eye(10) * 3.0)
        parent = etree(a.lower_triangle())
        assert (parent == -1).all()
        assert (etree_levels(parent) == 0).all()


class TestSymbolicAndNumeric:
    @given(st.integers(5, 80), st.floats(0.02, 0.3), st.integers(0, 8),
           st.sampled_from(["banded", "uniform", "blocky"]))
    @settings(max_examples=20, deadline=None)
    def test_factorization_matches_numpy(self, n, density, seed, pattern):
        a = _spd(n, density, seed, pattern)
        plan, vals, _ = cholesky(a, dtype=jnp.float64)
        l = plan_to_dense_l(plan, vals)
        ref = np.linalg.cholesky(a.to_dense())
        np.testing.assert_allclose(l, ref, rtol=1e-8, atol=1e-10)

    def test_reconstruction_property(self):
        a = _spd(60, 0.08, 42)
        plan, vals, _ = cholesky(a)
        l = plan_to_dense_l(plan, vals)
        np.testing.assert_allclose(l @ l.T, a.to_dense(), rtol=1e-8, atol=1e-9)

    def test_symbolic_pattern_covers_factor(self):
        a = _spd(50, 0.1, 7)
        plan = inspect_cholesky(a)
        ref = np.linalg.cholesky(a.to_dense())
        mask = np.zeros_like(ref, dtype=bool)
        col_of_slot = np.repeat(np.arange(plan.n), np.diff(plan.col_ptr))
        mask[plan.row_idx, col_of_slot] = True
        # every numerically nonzero entry of L is inside the symbolic pattern
        assert ((np.abs(ref) > 1e-12) <= mask).all()

    def test_levels_respect_dependencies(self):
        a = _spd(40, 0.15, 3)
        plan = inspect_cholesky(a)
        # every update's source column must be in a strictly earlier level
        col_of_slot = np.repeat(np.arange(plan.n), np.diff(plan.col_ptr))
        for ell in range(plan.n_levels):
            for src in (plan.upd_src1[ell], plan.upd_src2[ell]):
                src_lev = plan.levels[col_of_slot[src]]
                assert (src_lev < ell).all()

    def test_baseline_matches_executor(self):
        a = _spd(70, 0.07, 9)
        plan, vals, _ = cholesky(a)
        base_vals, _ = cholesky_baseline_numpy(plan, cholesky_values(a))
        np.testing.assert_allclose(vals, base_vals, rtol=1e-9, atol=1e-11)

    def test_fp32_mode(self):
        a = _spd(30, 0.1, 11)
        plan, vals, _ = cholesky(a, dtype=jnp.float32)
        l = plan_to_dense_l(plan, vals)
        np.testing.assert_allclose(l @ l.T, a.to_dense(), rtol=1e-3, atol=1e-3)

    def test_stats_report_split(self):
        a = _spd(50, 0.1, 13)
        _, _, stats = cholesky(a)
        assert stats["inspect_s"] > 0 and stats["execute_s"] > 0
        assert stats["n_levels"] >= 1


def _fresh_spd_values(a: CSR, seed: int) -> CSR:
    """Fresh SPD values on ``a``'s (symmetric) pattern: symmetric normal
    off-diagonals, diagonal 1 + the row's off-diagonal magnitudes."""
    rng = np.random.default_rng(seed)
    d = a.to_dense()
    r = rng.standard_normal(d.shape)
    off = np.where((d != 0) & ~np.eye(a.n_rows, dtype=bool), (r + r.T) / 2,
                   0.0)
    return CSR.from_dense(off + np.diag(1.0 + np.abs(off).sum(1)))


def _per_level_bundle(plan, ell: int):
    """Level ``ell``'s six index vectors built plainly: the cmod triples,
    the diagonal slots of its columns, their off-diagonal slots and the
    diagonal slot of each one's column, each padded with ``nnz`` to the
    power of two ≥ its length."""
    cols = plan.cols_per_level[ell]
    off = [np.arange(plan.col_ptr[c] + 1, plan.col_ptr[c + 1]) for c in cols]
    off_diag = [np.full(o.shape[0], plan.diag_pos[c])
                for o, c in zip(off, cols)]

    def pad(x):
        x = np.asarray(x, np.int64)
        size = 1
        while size < x.shape[0]:
            size *= 2
        out = np.full(size, plan.nnz, np.int64)
        out[:x.shape[0]] = x
        return out

    return [pad(plan.upd_src1[ell]), pad(plan.upd_src2[ell]),
            pad(plan.upd_dst[ell]), pad(plan.diag_pos[cols]),
            pad(np.concatenate(off)), pad(np.concatenate(off_diag))]


class TestLevelBundles:
    """The executor's per-plan memo of the packed level bundles
    (``CholeskyPlan.level_bundles`` / ``device_bundles``)."""

    @pytest.mark.parametrize("pattern", ["banded", "uniform", "blocky"])
    def test_layout_matches_per_level_construction(self, pattern):
        plan = inspect_cholesky(_spd(60, 0.1, 19, pattern))
        packed = plan.level_bundles()
        kind_group = (0, 0, 0, 1, 2, 2)     # src1/src2/dst, diag, off pair
        assert packed.buckets.shape == (plan.n_levels, 3)
        for k, flat in enumerate(packed[:6]):
            assert flat.dtype == np.int32
            # the levels' slices tile the flat array, in level order
            assert flat.shape[0] == packed.buckets[:, kind_group[k]].sum()
        at = np.zeros(3, np.int64)
        for ell in range(plan.n_levels):
            n = packed.buckets[ell]
            for k, want in enumerate(_per_level_bundle(plan, ell)):
                g = kind_group[k]
                np.testing.assert_array_equal(
                    packed[k][at[g]:at[g] + n[g]], want)
            at += n

    @pytest.mark.parametrize("pattern", ["banded", "uniform"])
    def test_cold_then_warm_factor_through_the_runtime(self, pattern):
        rt = ReapRuntime()
        a = _spd(70, 0.08, 17, pattern)
        plans, builds, puts = [], [], []
        for seed in (1, 2):
            a2 = _fresh_spd_values(a, seed)
            (plan, vals), st = rt.run("cholesky", a2)
            base, _ = cholesky_baseline_numpy(plan, plan.a_values(a2))
            np.testing.assert_allclose(vals, base, rtol=1e-12, atol=1e-13)
            plans.append(plan)
            builds.append(st.counters["cholesky_bundle_builds"])
            puts.append(st.counters["h2d_puts"])
            assert st.counters["launches"] == plan.n_levels
        assert plans[0] is plans[1]
        assert builds == [1, 0]
        # the values; the memo's six index arrays cold
        assert puts == [1 + 6, 1]

    def test_level_launches_send_nothing_from_the_host(self, monkeypatch):
        """Each level's step reads only device arrays: its slice starts
        come from the step before, its bucket lengths are static."""
        import jax
        import repro.runtime.pipeline as pl
        real = pl._level_step

        def guarded(*args):
            with jax.transfer_guard_host_to_device("disallow"):
                return real(*args)
        monkeypatch.setattr(pl, "_level_step", guarded)
        a = _spd(60, 0.1, 29, "uniform")
        plan = inspect_cholesky(a)
        a_vals = cholesky_values(a)
        for _ in range(2):                  # the build factor, then warm
            vals, _ = pl.cholesky_execute_overlapped(plan, a_vals)
            base, _ = cholesky_baseline_numpy(plan, a_vals)
            np.testing.assert_allclose(vals, base, rtol=1e-12, atol=1e-13)

    @pytest.mark.parametrize("via", ["serialize", "plan_store"])
    def test_round_trip_carries_no_memo_and_rebuilds_it(self, via,
                                                        tmp_path):
        from repro.core.inspector import fingerprint_pattern
        from repro.runtime import PlanStore, cholesky_execute_overlapped
        from repro.runtime.plan_cache import (_entry_nbytes,
                                              deserialize_plan,
                                              serialize_plan)
        a = _spd(50, 0.1, 23)
        a_vals = cholesky_values(a)
        plan = inspect_cholesky(a)
        nbytes = _entry_nbytes(plan)
        before, _ = cholesky_execute_overlapped(plan, a_vals)
        assert _entry_nbytes(plan) == nbytes      # the memo is not counted
        if via == "serialize":
            back = deserialize_plan(serialize_plan(plan))
        else:
            fp = fingerprint_pattern("cholesky", (a,))
            PlanStore(tmp_path).put(fp, plan)
            back = PlanStore(tmp_path).get(fp)
        assert getattr(back, "_device_bundles", None) is None  # not stored
        builds = []
        for _ in range(2):
            with spans.record("reap.run") as rec:
                after, _ = cholesky_execute_overlapped(back, a_vals)
            builds.append(rec.counters["cholesky_bundle_builds"])
            np.testing.assert_array_equal(after, before)
        assert builds == [1, 0]
        rebuilt = back.device_bundles(put=None)
        original = plan.device_bundles(put=None)
        assert rebuilt is not original
        for got, want in zip(rebuilt, original):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
