"""``chip_smoke.py`` rehearsed on the CPU: every phase function at a tiny
size against its reference, and the script's refusal to report a result
without a TPU or outside a checkout."""
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("spgemm_id,method,executor", [
    ("S11", "auto", "block"), ("S8", "gather", "gather")])
def test_phase_spgemm(smoke, spgemm_id, method, executor):
    info = smoke.phase_spgemm(spgemm_id, method, k=500)
    assert info["ok"], info
    assert info["executor"].startswith(executor)
    assert info["warm_cache_hit"] and info["warm_bitwise_equal_cold"]
    assert info["rel_fro_err"] <= info["bound"]


def test_phase_cholesky(smoke):
    info = smoke.phase_cholesky(k=40)
    assert info["ok"], info
    assert info["rel_max_err"] <= smoke.CHOLESKY_TOL


def test_phase_cg(smoke):
    info = smoke.phase_cg(k=40)
    assert info["ok"], info
    assert len(info["solves"]) == smoke.CG_SOLVES
    assert info["spmv_misses"] == 1 and info["spmv_hits"] > 0
    assert not info["pallas_tpu_custom_call"]        # interpreter on CPU


def test_phase_serve(smoke):
    info = smoke.phase_serve(reduced=True, requests=4, max_batch=2)
    assert info["ok"], info
    assert info["completions"] == 4 and info["tokens"] > 0
    assert info["logsoftmax_rel_err"] <= smoke.LOGSOFTMAX_TOL


@pytest.mark.parametrize("phase", ["phase_sharded_spgemm",
                                   "phase_sharded_spmm"])
def test_phase_sharded(smoke, phase):
    n = len(jax.devices())
    info = getattr(smoke, phase)(n, k=500)
    assert info["ok"], info
    assert info["bitwise_equal"]


def test_no_result_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    assert '"ok": true' not in capsys.readouterr().out


def test_no_result_outside_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
