"""Bring-up smoke test: the main paths of this repository on a TPU.

    python3 chip_smoke.py            # one chip: phases (a)-(d)
    python3 chip_smoke.py --chips 4  # four chips: sharded phases only

One chip, each phase through the calls a user makes, at published sizes:

  (a) SpGEMM C = A·A through ``ReapRuntime.run("spgemm", a, a)``, cold
      then warm, on the Table I stand-ins S11 ``cant`` and S8
      ``2cubes_sphere`` — one on each executor (block/Pallas-MXU and
      gather/VPU) — against ``spgemm_ref_numpy``;
  (b) sparse Cholesky through ``ReapRuntime.run("cholesky", a)`` on C3
      ``bcsstk17`` at float64, against ``cholesky_baseline_numpy``;
  (c) three planned CG solves (``cg_solve``) with new values on one SPD
      pattern of that size, float32 matvecs on the Pallas ``bsr_spmm``
      kernel, against scipy's direct solve;
  (d) continuous serving of ``qwen3-1.7b`` at its published widths and
      depth through ``repro.launch.serve.main``, plus one prompt's prefill
      log-softmax against the same parameters at float32 with
      ``precision=HIGHEST``.

Four chips: sharded gather-SpGEMM on S8 and sharded SpMM through
``ReapRuntime(RuntimeConfig(mesh_shape=(4,)))``, each compared bit for bit
with the same op on one of the chips.

Each phase prints one ``phase {...}`` JSON line (shapes, dtypes, executor
path, compile count, wall seconds, correctness figure and bound,
``peak_bytes_in_use``).  The last line of standard output is
``{"ok": true, "device": {...}}`` and the exit code 0 only when every phase
matched its reference.  Without a TPU, or outside a checkout of this
repository, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: correctness bounds, stated before any chip run
SPGEMM_TOL = 1e-5           # rel. Frobenius error: f32 products, f32 sums
CHOLESKY_TOL = 1e-8         # max |ΔL| / max |L| at float64
CG_TOL = 1e-5               # CG relative residual target (f32 matvecs)
CG_CHECK_TOL = 1e-4         # ‖x − x_scipy‖/‖x_scipy‖ and ‖Ax − b‖/‖b‖
LOGSOFTMAX_TOL = 1e-2       # ‖Δ log-softmax‖₂ / ‖log-softmax‖₂, bf16 vs f32

SERVE_ARCH = "qwen3-1.7b"
CG_SOLVES = 3

_COMPILES = [0]


def _count_compiles() -> None:
    """Count XLA backend compilations (persistent-cache hits are not)."""
    import jax

    def listener(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _spec(spgemm_id=None, chol_id=None):
    from benchmarks.table1 import TABLE1
    return next(m for m in TABLE1
                if (spgemm_id and m.spgemm_id == spgemm_id)
                or (chol_id and m.chol_id == chol_id))


def _csr_desc(a) -> str:
    return f"{a.n_rows}x{a.n_cols} nnz={a.nnz} {np.dtype(a.data.dtype).name}"


def _scipy(a, dtype=np.float64):
    import scipy.sparse as sp
    return sp.csr_matrix((np.asarray(a.data, dtype), a.indices, a.indptr),
                         shape=(a.n_rows, a.n_cols))


def _rel_fro(c, ref) -> float:
    """‖C − R‖_F / ‖R‖_F over the union of both patterns."""
    import scipy.sparse.linalg as spla
    r = _scipy(ref)
    return float(spla.norm(_scipy(c) - r) / max(spla.norm(r), 1e-300))


def _is_native(lowered) -> bool:
    """True when a lowered program compiles to a Mosaic kernel call."""
    return "tpu_custom_call" in lowered.compile().as_text()


def _native_ok(native: bool) -> bool:
    """A kernel check passes when the kernel is native, or when there is no
    TPU to run it natively on (the phases rehearsed on the CPU)."""
    import jax
    return native or jax.default_backend() != "tpu"


def _sds(shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


# ---------------------------------------------------------------------------
# Phases (each returns a dict with an "ok" key; a raise fails the phase)
# ---------------------------------------------------------------------------

def phase_spgemm(spgemm_id: str, method: str = "auto", *, k: int = 1,
                 seed: int = 0) -> dict:
    """(a) C = A·A through the runtime, cold then warm."""
    from benchmarks.table1 import make_spgemm_matrix
    from repro.core import CSR, spgemm_ref_numpy
    from repro.runtime import ReapRuntime

    spec = _spec(spgemm_id=spgemm_id)
    a, _ = make_spgemm_matrix(spec, seed, k=k)
    rt = ReapRuntime()
    t0 = time.perf_counter()
    c_cold, s_cold = rt.run("spgemm", a, a, method=method)
    t1 = time.perf_counter()
    c_warm, s_warm = rt.run("spgemm", a, a, method=method)
    t2 = time.perf_counter()
    executor = "block" if "block" in s_cold["method"] else "gather"

    a64 = CSR(a.n_rows, a.n_cols, a.indptr, a.indices,
              a.data.astype(np.float64))
    err = _rel_fro(c_warm, spgemm_ref_numpy(a64, a64))
    warm_same = (np.array_equal(c_cold.indptr, c_warm.indptr)
                 and np.array_equal(c_cold.indices, c_warm.indices)
                 and np.array_equal(c_cold.data, c_warm.data))
    info = dict(matrix=f"{spgemm_id} {spec.name}", a=_csr_desc(a),
                c=_csr_desc(c_warm), method=method,
                executor=f"{executor} ({s_cold['method']}, "
                         f"{s_cold.get('n_chunks', 1)} chunks)",
                cold_s=t1 - t0, warm_s=t2 - t1,
                warm_cache_hit=bool(s_warm["cache_hit"]),
                warm_bitwise_equal_cold=warm_same,
                rel_fro_err=err, bound=SPGEMM_TOL)
    ok = err <= SPGEMM_TOL and warm_same and s_warm["cache_hit"]
    if executor == "block":
        info.update(_block_kernel_checks(a, rt.config))
        ok = ok and info["split_launches_bitwise_equal"] and _native_ok(
            info["pallas_tpu_custom_call"])
    info["ok"] = bool(ok)
    return info


def _block_kernel_checks(a, cfg) -> dict:
    """The block kernel as the runtime launches it, on this device.

    * the runtime's first chunk, lowered with its own shapes and static
      arguments: on a TPU it must compile to the Mosaic kernel;
    * the whole schedule cut into several launches (cuts inside output
      groups) must equal the one-launch result bit for bit — the carry
      between launches is what keeps long schedules inside SMEM.
    """
    import jax.numpy as jnp
    from repro.core.inspector import inspect_spgemm_block
    from repro.kernels.bsr_spgemm import bsr_spgemm
    from repro.runtime.pipeline import build_block_chunkset, \
        bucket_block_schedule

    bs = cfg.block
    plan = inspect_spgemm_block(a, a, bs)
    sched = bucket_block_schedule(
        build_block_chunkset(plan, cfg.n_chunks).chunk(0))
    ids = [_sds((sched["pair_cap"],), jnp.int32)] * 5
    native = _is_native(bsr_spgemm.lower(
        _sds((sched["a_cap"], bs, bs), jnp.float32),
        _sds((sched["b_cap"], bs, bs), jnp.float32), *ids,
        n_out_blocks=sched["out_cap"] + 1))

    blocks = jnp.asarray(plan.a_pat.scatter(a.data), jnp.float32)
    args = (blocks, blocks) + tuple(
        jnp.asarray(getattr(plan, f), jnp.int32)
        for f in ("a_id", "b_id", "out_id", "is_first", "is_last"))
    launch_pairs = max(1, plan.n_pairs // 3)
    whole = bsr_spgemm(*args, n_out_blocks=plan.n_out_blocks)
    split = bsr_spgemm(*args, n_out_blocks=plan.n_out_blocks,
                       launch_pairs=launch_pairs)
    return dict(pallas_tpu_custom_call=native,
                split_launches=-(-plan.n_pairs // launch_pairs),
                split_launches_bitwise_equal=_bitwise(whole, split))


def phase_cholesky(*, k: int = 1, seed: int = 0) -> dict:
    """(b) A = L Lᵀ through the runtime at the op's default float64."""
    from benchmarks.table1 import make_chol_matrix
    from repro.core import cholesky_baseline_numpy
    from repro.runtime import ReapRuntime

    spec = _spec(chol_id="C3")
    a, _ = make_chol_matrix(spec, seed, k=k)
    rt = ReapRuntime()
    t0 = time.perf_counter()
    (plan, vals), st = rt.run("cholesky", a)
    run_s = time.perf_counter() - t0
    vals = np.asarray(vals)
    ref, _ = cholesky_baseline_numpy(plan, plan.a_values(a))
    err = float(np.max(np.abs(vals - ref)) / np.max(np.abs(ref)))
    return dict(matrix=f"{spec.chol_id} {spec.name}", a=_csr_desc(a),
                l=f"nnz={plan.nnz} levels={plan.n_levels} "
                  f"{vals.dtype.name}",
                executor="etree level steps (XLA gather/scatter) over "
                         "the plan's packed bundles on the device",
                inspect_s=st["inspect_s"], run_s=run_s,
                execute_s=st.get("execute_s"), rel_max_err=err,
                bound=CHOLESKY_TOL, ok=bool(err <= CHOLESKY_TOL))


def phase_cg(*, k: int = 1, seed: int = 0) -> dict:
    """(c) planned CG: new values on one SPD pattern, float32 matvecs."""
    import jax.numpy as jnp
    import scipy.sparse.linalg as spla
    from benchmarks.table1 import make_chol_matrix
    from repro.core import CSR
    from repro.core.solver import cg_solve, inspect_spmv
    from repro.kernels.bsr_spmm import bsr_spmm
    from repro.runtime import ReapRuntime

    spec = _spec(chol_id="C3")
    a0, _ = make_chol_matrix(spec, seed + 1, k=k)
    rt = ReapRuntime()
    rng = np.random.default_rng(seed)
    solves, ok = [], True
    for step in range(CG_SOLVES):
        a = CSR(a0.n_rows, a0.n_cols, a0.indptr, a0.indices,
                a0.data * (1.0 + 0.1 * step))
        b = rng.standard_normal(a.n_rows)
        t0 = time.perf_counter()
        x, info = cg_solve(a, b, rt, tol=CG_TOL, dtype=np.float32)
        solve_s = time.perf_counter() - t0
        a_sp = _scipy(a)
        x_ref = spla.spsolve(a_sp.tocsc(), b)
        err = float(np.linalg.norm(x - x_ref) / np.linalg.norm(x_ref))
        res = float(np.linalg.norm(a_sp @ x - b) / np.linalg.norm(b))
        ok = ok and info["converged"] and err <= CG_CHECK_TOL \
            and res <= CG_CHECK_TOL
        solves.append(dict(iterations=info["iterations"], solve_s=solve_s,
                           spmv_cache_hits=info["spmv_cache_hits"],
                           rel_err_vs_scipy=err, rel_residual=res))
    per_op = rt.cache_stats()["per_op"]["spmv"]
    ok = ok and per_op["misses"] == 1

    # the matvec kernel as cg_solve launched it: on a TPU, the Mosaic kernel
    inner = inspect_spmv(a0, rt.config.block).inner
    ids = [_sds((inner.n_jobs,), jnp.int32)] * 5
    lowered = bsr_spmm.lower(
        _sds((1, inner.pat.n_rows), jnp.float32),
        _sds((inner.pat.n_blocks + 1, inner.block, inner.block),
             jnp.float32), *ids, n_j_blocks=inner.n_j_blocks, bt=1)
    native = _is_native(lowered)
    return dict(matrix=f"SPD pattern sized as {spec.chol_id} {spec.name}",
                a=_csr_desc(a0), matvec="float32 spmv op",
                executor=f"bsr_spmm Pallas, {inner.n_jobs} jobs",
                pallas_tpu_custom_call=native, solves=solves,
                spmv_misses=per_op["misses"], spmv_hits=per_op["hits"],
                tol=CG_TOL, bound=CG_CHECK_TOL,
                ok=bool(ok and _native_ok(native)))


def phase_serve(*, reduced: bool = False, seed: int = 0,
                requests: int = 8, max_batch: int = 4) -> dict:
    """(d) continuous serving through serve.main + a prefill reference,
    with 64-bit types off as ``python -m repro.launch.serve`` runs."""
    import jax
    with jax.enable_x64(False):
        return _serve(reduced, seed, requests, max_batch)


def _serve(reduced, seed, requests, max_batch) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, reduced_config
    from repro.launch import serve
    from repro.launch.scheduler import synthetic_trace
    from repro.models import model as M

    argv = ["--arch", SERVE_ARCH, "--reduced" if reduced else "--no-reduced",
            "--continuous", "--requests", str(requests),
            "--max-batch", str(max_batch),
            "--expect-completions", str(requests), "--seed", str(seed)]
    t0 = time.perf_counter()
    completions = serve.main(argv)          # SystemExit on any failed gate
    serve_s = time.perf_counter() - t0
    n_tokens = sum(len(c.tokens) for c in completions)
    gc.collect()                            # serve's parameters are freed

    cfg = get_config(SERVE_ARCH)
    if reduced:
        cfg = reduced_config(cfg)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))   # serve's params
    prompt = synthetic_trace(requests, seed=seed, vocab=cfg.vocab_size)[0]
    toks = jnp.asarray(prompt.prompt[None, :], jnp.int32)

    def prefill_logsoftmax(c):
        logits, _ = jax.jit(lambda p, t, cache: M.prefill(c, p, t, cache))(
            params, toks, M.init_cache(c, 1, 64))
        return np.asarray(jax.nn.log_softmax(logits[0], axis=-1), np.float64)

    test = prefill_logsoftmax(cfg)
    with jax.default_matmul_precision("highest"):
        ref = prefill_logsoftmax(dataclasses.replace(cfg,
                                                     compute_dtype="float32"))
    err = float(np.linalg.norm(test - ref) / np.linalg.norm(ref))
    agree = float(np.mean(test.argmax(-1) == ref.argmax(-1)))
    return dict(arch=SERVE_ARCH,
                config=f"layers={cfg.n_layers} d_model={cfg.d_model} "
                       f"vocab={cfg.vocab_size} params={cfg.param_dtype} "
                       f"compute={cfg.compute_dtype}",
                executor="serve.main --continuous (ServeScheduler)",
                requests=requests, max_batch=max_batch,
                completions=len(completions), tokens=n_tokens,
                serve_s=serve_s, prompt_len=int(toks.shape[1]),
                logsoftmax_rel_err=err, logsoftmax_max_abs_err=float(
                    np.max(np.abs(test - ref))),
                argmax_agreement=agree, bound=LOGSOFTMAX_TOL,
                ok=bool(len(completions) == requests
                        and err <= LOGSOFTMAX_TOL))


def _bitwise(x, y) -> bool:
    return (np.asarray(x).shape == np.asarray(y).shape
            and np.array_equal(np.asarray(x), np.asarray(y)))


def phase_sharded_spgemm(n_shards: int, *, k: int = 1, seed: int = 0
                         ) -> dict:
    """Sharded gather-SpGEMM on S8 vs the same op on one chip."""
    from benchmarks.table1 import make_spgemm_matrix
    from repro.runtime import ReapRuntime, RuntimeConfig

    spec = _spec(spgemm_id="S8")
    a, _ = make_spgemm_matrix(spec, seed, k=k)
    rt_mesh = ReapRuntime(RuntimeConfig(mesh_shape=(n_shards,)))
    t0 = time.perf_counter()
    c_sh, s_sh = rt_mesh.run("spgemm", a, a, method="gather")
    t1 = time.perf_counter()
    c_one, s_one = ReapRuntime().run("spgemm", a, a, method="gather")
    t2 = time.perf_counter()
    same = (_bitwise(c_sh.indptr, c_one.indptr)
            and _bitwise(c_sh.indices, c_one.indices)
            and _bitwise(c_sh.data, c_one.data))
    return dict(matrix=f"S8 {spec.name}", a=_csr_desc(a),
                executor=f"{s_sh['method']} over {s_sh.get('n_shards')} "
                         f"shards vs {s_one['method']} on one chip",
                sharded_s=t1 - t0, one_chip_s=t2 - t1,
                bitwise_equal=same, ok=bool(
                    same and s_sh.get("n_shards") == n_shards))


def phase_sharded_spmm(n_shards: int, *, k: int = 1, seed: int = 0,
                       tokens: int = 128) -> dict:
    """Sharded SpMM (W = S8) vs the same tile math on one chip."""
    from benchmarks.table1 import make_spgemm_matrix
    from repro.runtime import ReapRuntime, RuntimeConfig

    spec = _spec(spgemm_id="S8")
    w, _ = make_spgemm_matrix(spec, seed, k=k)
    x = np.random.default_rng(seed).standard_normal(
        (tokens, w.n_rows)).astype(np.float32)
    rt_mesh = ReapRuntime(RuntimeConfig(mesh_shape=(n_shards,)))
    t0 = time.perf_counter()
    y_sh, s_sh = rt_mesh.run("spmm", x, w)
    t1 = time.perf_counter()
    # the sharded executor runs the jnp tile math (_spmm_math), so the
    # one-chip comparison is the same op on that executor
    y_one, _ = ReapRuntime(use_pallas=False).run("spmm", x, w)
    t2 = time.perf_counter()
    same = _bitwise(y_sh, y_one)
    return dict(matrix=f"W = S8 {spec.name}", w=_csr_desc(w),
                x=f"{tokens}x{w.n_rows} float32",
                executor=f"{s_sh['method']} over {s_sh.get('n_shards')} "
                         "shards vs spmm (use_pallas=False) on one chip",
                sharded_s=t1 - t0, one_chip_s=t2 - t1,
                bitwise_equal=same, ok=bool(
                    same and s_sh.get("n_shards") == n_shards))


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def run_phase(name: str, fn, *args, **kw) -> dict:
    """Run one phase, print its line, return its info (``ok`` included)."""
    c0, t0 = _COMPILES[0], time.perf_counter()
    try:
        info = fn(*args, **kw)
    except (Exception, SystemExit) as e:      # a failed gate fails the phase
        traceback.print_exc(file=sys.stderr)
        info = dict(error=f"{type(e).__name__}: {e}", ok=False)
    info = dict(phase=name, **info, compiles=_COMPILES[0] - c0,
                wall_s=time.perf_counter() - t0,
                peak_bytes_in_use=_peak_bytes())
    print("phase " + json.dumps(info, default=str), flush=True)
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases (a)-(d) on one chip; 4: only the "
                         "sharded phases and their one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        from repro.launch.compile_cache import init_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    init_compile_cache()
    jax.config.update("jax_enable_x64", True)   # Cholesky runs at float64
    _count_compiles()

    if args.chips == 4:
        infos = [run_phase("sharded_spgemm_gather", phase_sharded_spgemm, 4,
                           seed=args.seed),
                 run_phase("sharded_spmm", phase_sharded_spmm, 4,
                           seed=args.seed)]
    else:
        first = run_phase("spgemm_S11", phase_spgemm, "S11",
                          seed=args.seed)
        # both executors at published size: S8 runs the one that
        # method="auto" did not pick for S11
        method8 = ("gather" if first.get("executor", "").startswith("block")
                   else "block")
        infos = [first,
                 run_phase("spgemm_S8", phase_spgemm, "S8", method8,
                           seed=args.seed),
                 run_phase("cholesky", phase_cholesky, seed=args.seed),
                 run_phase("cg", phase_cg, seed=args.seed),
                 run_phase("serve", phase_serve, seed=args.seed)]
        if {i.get("executor", "").split(" ")[0] for i in infos[:2]} \
                != {"block", "gather"}:
            print("chip_smoke: the SpGEMM phases did not cover both "
                  "executors", file=sys.stderr)
            return 1
    if not all(i["ok"] for i in infos):
        print("chip_smoke: FAILED: " + ", ".join(
            i["phase"] for i in infos if not i["ok"]), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
