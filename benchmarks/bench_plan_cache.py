"""Plan-cache benchmark: the claims of repro.runtime.

1. **cold vs warm** — on a repeated-pattern workload (same sparsity,
   fresh values each call: iterative solvers, MoE dispatch, the Fig-10
   sweep), a warm plan cache must make end-to-end SpGEMM ≥ 2× faster than
   paying the inspector every call; the registry-admitted ``spmm`` and
   ``block_attention`` ops (whose inspectors are intrinsically lighter)
   must be ≥ 1.4× warm.
2. **per-op coverage** — every tag in ``runtime.ops.list_ops()`` with an
   example problem (the shared ``repro.analysis.op_examples`` table, also
   replayed by the purity harness) is run miss-then-hit through one
   runtime and its
   ``cache_stats()["per_op"]`` split is reported, so the benchmark output
   enumerates coverage from the op registry instead of a hard-coded list.

Prints ``plan_cache,...`` CSV lines and a PASS/FAIL verdict per claim, and
exits non-zero when a claim fails (the bench.yml CI gate).  In
``--reduced`` (CI) mode problem sizes shrink.

    PYTHONPATH=src python -m benchmarks.bench_plan_cache [--reduced]
        [--json OUT]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import List, Optional

import numpy as np

import jax.numpy as jnp

from repro.core import CSR, random_csr, random_spd_csr
from repro.launch.compile_cache import init_compile_cache
from repro.runtime import ReapRuntime, RuntimeConfig, add_runtime_args

# per-op coverage is registry-driven and shared with fig6/fig10 (and the
# analysis purity harness) — see op_coverage / repro.analysis.op_examples
from .op_coverage import per_op_breakdown  # noqa: F401  (re-export)

# CLI-derived base config (main() replaces it via RuntimeConfig.from_args);
# each bench overrides only the knobs it is *about* (n_chunks, …)
_BASE_CFG = RuntimeConfig()


def _revalue(a: CSR, rng: np.random.Generator) -> CSR:
    """Same pattern, fresh values — the repeated-pattern workload step."""
    return CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
               rng.standard_normal(a.nnz).astype(a.data.dtype))


def _bench_runtime(method: str, n_chunks: int) -> ReapRuntime:
    # block path: jnp executor (Pallas interpret mode on this container would
    # time the Python interpreter, not the schedule), modest MXU tile
    kw = dict(use_pallas=False, block=64) if method == "block" else {}
    return ReapRuntime(_BASE_CFG, n_chunks=n_chunks, **kw)


def _matrices(method: str, n: int, density: float, seed: int):
    rng = np.random.default_rng(seed)
    pattern = "blocky" if method == "block" else "uniform"
    return rng, random_csr(n, n, density, rng, pattern), \
        random_csr(n, n, density, rng, pattern)


def bench_spgemm_cache(n: int = 2000, density: float = 0.01,
                       repeats: int = 5, method: str = "gather",
                       verbose: bool = True) -> dict:
    rng, a, b = _matrices(method, n, density, 0)

    # cold: a fresh runtime per call ⇒ every call re-inspects
    cold_s: List[float] = []
    for _ in range(repeats):
        a, b = _revalue(a, rng), _revalue(b, rng)
        rt = _bench_runtime(method, n_chunks=1)
        t0 = time.perf_counter()
        rt.spgemm(a, b, method=method)
        cold_s.append(time.perf_counter() - t0)

    # warm: one runtime; first call populates, the rest hit
    rt = _bench_runtime(method, n_chunks=1)
    rt.spgemm(a, b, method=method)              # populate
    warm_s: List[float] = []
    for _ in range(repeats):
        a, b = _revalue(a, rng), _revalue(b, rng)
        t0 = time.perf_counter()
        _, st = rt.spgemm(a, b, method=method)
        warm_s.append(time.perf_counter() - t0)
        assert st["cache_hit"], "pattern unchanged — must hit"

    # min over repeats on both sides: the interference-free cost of each
    # mode (co-tenant load spikes inflate medians asymmetrically; a real
    # warm-path regression still raises min(warm) on every repeat)
    cold, warm = float(np.min(cold_s)), float(np.min(warm_s))
    speedup = cold / max(warm, 1e-9)
    row = dict(bench=f"spgemm_{method}_cold_vs_warm", n=n, density=density,
               cold_s=cold, warm_s=warm, speedup=speedup,
               ok=speedup >= 2.0)
    if verbose:
        print(f"plan_cache,spgemm_{method},n={n},cold_ms={cold * 1e3:.1f},"
              f"warm_ms={warm * 1e3:.1f},speedup={speedup:.2f},"
              f"{'PASS' if row['ok'] else 'FAIL'}(>=2x)")
    return row


def bench_spmm_cache(n: int = 4096, density: float = 0.02, t: int = 32,
                     repeats: int = 5, verbose: bool = True) -> dict:
    """Cold vs warm for the registry-admitted ``spmm`` op (Y = X @ W_sparse).

    W's pattern is fixed across calls (a frozen sparse weight); X is fresh
    dense values each call — the per-microbatch serving workload.  SpMM's
    inspector (one BSR pattern + job sort) is intrinsically cheaper
    relative to its executor than SpGEMM's Gustavson expansion, so the
    gate is ≥ 1.4× (typical ~2×) rather than the SpGEMM paths' 2×.
    """
    rng = np.random.default_rng(3)
    w = random_csr(n, n, density, rng, "blocky")

    def fresh_x():
        return rng.standard_normal((t, n)).astype(np.float32)

    cold_s: List[float] = []
    for _ in range(repeats):
        w = _revalue(w, rng)
        rt = _bench_runtime("block", n_chunks=1)
        t0 = time.perf_counter()
        rt.run("spmm", fresh_x(), w)
        cold_s.append(time.perf_counter() - t0)

    rt = _bench_runtime("block", n_chunks=1)
    rt.run("spmm", fresh_x(), w)                # populate
    warm_s: List[float] = []
    for _ in range(repeats):
        w = _revalue(w, rng)
        t0 = time.perf_counter()
        _, st = rt.run("spmm", fresh_x(), w)
        warm_s.append(time.perf_counter() - t0)
        assert st["cache_hit"], "W pattern unchanged — must hit"

    cold, warm = float(np.min(cold_s)), float(np.min(warm_s))
    speedup = cold / max(warm, 1e-9)
    row = dict(bench="spmm_cold_vs_warm", n=n, density=density, t=t,
               cold_s=cold, warm_s=warm, speedup=speedup,
               ok=speedup >= 1.4)
    if verbose:
        print(f"plan_cache,spmm,n={n},cold_ms={cold * 1e3:.1f},"
              f"warm_ms={warm * 1e3:.1f},speedup={speedup:.2f},"
              f"{'PASS' if row['ok'] else 'FAIL'}(>=1.4x)")
    return row


def bench_block_attention(seq: int = 4096, density: float = 0.05,
                          heads: int = 1, head_dim: int = 32,
                          repeats: int = 5, verbose: bool = True) -> dict:
    """Cold vs warm for the registry-admitted ``block_attention`` op.

    The block-sparse mask's *pattern* is fixed across calls (a frozen
    attention structure: sliding-window + global tokens, document masks);
    q/k/v are fresh values each call — the per-batch serving workload.
    Cold pays the BSR mask lowering (bsr_pattern_from_csr + kv_ids
    padding) every call; warm replays the cached plan.  Like ``spmm``
    the inspector-to-executor ratio is moderate, so the gate is ≥ 1.4×.
    """
    rng = np.random.default_rng(4)
    mask = random_csr(seq, seq, density, rng, "blocky")

    def fresh_qkv():
        q = rng.standard_normal((1, heads, seq, head_dim)).astype(np.float32)
        k = rng.standard_normal((1, heads, seq, head_dim)).astype(np.float32)
        v = rng.standard_normal((1, heads, seq, head_dim)).astype(np.float32)
        return q, k, v

    cold_s: List[float] = []
    for _ in range(repeats):
        mask = _revalue(mask, rng)              # same pattern, fresh bytes
        q, k, v = fresh_qkv()
        rt = _bench_runtime("block", n_chunks=1)
        t0 = time.perf_counter()
        rt.run("block_attention", q, k, v, mask)
        cold_s.append(time.perf_counter() - t0)

    rt = _bench_runtime("block", n_chunks=1)
    rt.run("block_attention", *fresh_qkv(), mask)   # populate
    warm_s: List[float] = []
    for _ in range(repeats):
        mask = _revalue(mask, rng)
        q, k, v = fresh_qkv()
        t0 = time.perf_counter()
        _, st = rt.run("block_attention", q, k, v, mask)
        warm_s.append(time.perf_counter() - t0)
        assert st["cache_hit"], "mask pattern unchanged — must hit"

    cold, warm = float(np.min(cold_s)), float(np.min(warm_s))
    speedup = cold / max(warm, 1e-9)
    row = dict(bench="block_attention_cold_vs_warm", seq=seq,
               density=density, heads=heads, head_dim=head_dim,
               cold_s=cold, warm_s=warm, speedup=speedup,
               ok=speedup >= 1.4)
    if verbose:
        print(f"plan_cache,block_attention,seq={seq},"
              f"cold_ms={cold * 1e3:.1f},warm_ms={warm * 1e3:.1f},"
              f"speedup={speedup:.2f},"
              f"{'PASS' if row['ok'] else 'FAIL'}(>=1.4x)")
    return row


def bench_cholesky(n: int = 900, density: float = 0.01, repeats: int = 3,
                   verbose: bool = True) -> dict:
    rng = np.random.default_rng(2)
    a = random_spd_csr(n, density, rng)

    cold_s = []
    for _ in range(repeats):
        rt = ReapRuntime(_BASE_CFG)
        t0 = time.perf_counter()
        rt.cholesky(a, dtype=jnp.float32)
        cold_s.append(time.perf_counter() - t0)

    rt = ReapRuntime(_BASE_CFG)
    rt.cholesky(a, dtype=jnp.float32)
    warm_s = []
    for _ in range(repeats):
        scaled = CSR(a.n_rows, a.n_cols, a.indptr, a.indices, a.data * 1.01)
        t0 = time.perf_counter()
        _, _, st = rt.cholesky(scaled, dtype=jnp.float32)
        warm_s.append(time.perf_counter() - t0)
        assert st["cache_hit"]

    cold, warm = float(np.median(cold_s)), float(np.median(warm_s))
    row = dict(bench="cholesky", n=n, cold_s=cold, warm_s=warm,
               speedup=cold / max(warm, 1e-9))
    if verbose:
        print(f"plan_cache,cholesky,n={n},cold_ms={cold * 1e3:.1f},"
              f"warm_ms={warm * 1e3:.1f},"
              f"warm_speedup={row['speedup']:.2f}")
    return row


def run(verbose: bool = True, reduced: bool = False) -> List[dict]:
    if reduced:
        rows = [bench_spgemm_cache(n=1200, verbose=verbose),
                bench_spgemm_cache(method="block", n=1200, density=0.02,
                                   repeats=7, verbose=verbose),
                bench_cholesky(n=600, verbose=verbose),
                # spmm and block_attention keep their full sizes even in
                # reduced mode: their gates need the inspector/executor
                # ratio scale provides, and each row costs ~1 s of wall
                bench_spmm_cache(verbose=verbose),
                bench_block_attention(verbose=verbose),
                per_op_breakdown(reduced=True, verbose=verbose)]
    else:
        rows = [bench_spgemm_cache(verbose=verbose),
                bench_spgemm_cache(method="block", density=0.02, repeats=9,
                                   verbose=verbose),
                bench_cholesky(verbose=verbose),
                bench_spmm_cache(verbose=verbose),
                bench_block_attention(verbose=verbose),
                per_op_breakdown(verbose=verbose)]
    if verbose:
        ok = all(r.get("ok", True) for r in rows)
        print(f"plan_cache,verdict,{'PASS' if ok else 'FAIL'}")
    return rows


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reduced", action="store_true",
                    help="smaller problem sizes (CI mode)")
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="write result rows to this JSON file")
    add_runtime_args(ap)
    args = ap.parse_args(argv)
    init_compile_cache()
    global _BASE_CFG
    _BASE_CFG = RuntimeConfig.from_args(args)
    rows = run(reduced=args.reduced)
    if args.json:
        Path(args.json).write_text(json.dumps(
            dict(bench="plan_cache", reduced=args.reduced, rows=rows),
            indent=1))
    return 0 if all(r.get("ok", True) for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
