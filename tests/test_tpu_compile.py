"""The main-path kernels compiled for a described (not attached) TPU v5e.

Nothing runs: each test lowers a kernel or executor at the shapes the
chip smoke test (``chip_smoke.py``) uses and compiles it with the TPU
compiler for a ``v5e:2x2`` topology, which refuses what the Pallas
interpreter accepts — SMEM or VMEM overflow, misaligned tiles.  The
topology is described inside a module fixture, and the tests skip where it
cannot be described.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.cholesky import _level_step
from repro.core.spgemm import _gather_execute_capped
from repro.kernels.bsr_spgemm import LAUNCH_PAIRS, bsr_spgemm
from repro.kernels.bsr_spmm import bsr_spmm


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # executables for a described chip cannot be read back from the
    # persistent cache here; keep them out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    sharding = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sharding)


def test_block_spgemm_long_schedule(one_chip):
    """300,000 pairs (more than 262,144) compile: each launch keeps its
    scalar-prefetch schedule inside SMEM."""
    n_pairs, bs = 300_000, 128
    ids = [one_chip((n_pairs,), jnp.int32)] * 5
    compiled = bsr_spgemm.lower(
        one_chip((4096, bs, bs), jnp.float32),
        one_chip((4096, bs, bs), jnp.float32), *ids,
        n_out_blocks=20_000, interpret=False).compile()
    n_launches = -(-n_pairs // LAUNCH_PAIRS)
    assert compiled.as_text().count("tpu_custom_call") >= n_launches


def test_bsr_spmm_cg_matvec(one_chip):
    """The float32 matvec kernel at the CG phase's shape (C3-sized SPD)."""
    from benchmarks.table1 import TABLE1, make_chol_matrix
    from repro.core.solver import inspect_spmv
    spec = next(m for m in TABLE1 if m.chol_id == "C3")
    a, _ = make_chol_matrix(spec, 1, k=1)
    plan = inspect_spmv(a, 128).inner
    ids = [one_chip((plan.n_jobs,), jnp.int32)] * 5
    compiled = bsr_spmm.lower(
        one_chip((1, plan.pat.n_rows), jnp.float32),
        one_chip((plan.pat.n_blocks + 1, 128, 128), jnp.float32), *ids,
        n_j_blocks=plan.n_j_blocks, bt=1, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gather_executor(one_chip):
    """One chunk of the S8 gather SpGEMM (2**23 partial products)."""
    pp = c_cap = 1 << 23
    idx = one_chip((pp,), jnp.int64)
    _gather_execute_capped.lower(
        one_chip((409_600,), jnp.float32), one_chip((1_640_000,), jnp.float32),
        idx, idx, idx, c_cap=c_cap).compile()


def test_gather_executor_cop20k_chunk(one_chip):
    """One of four chunks of the cop20K stand-in's A² (about 20M partial
    products, bucketed to 2**25 slots; about 4.7M outputs, to 2**23)."""
    idx = one_chip((1 << 25,), jnp.int64)
    compiled = _gather_execute_capped.lower(
        one_chip((656_083,), jnp.float32), one_chip((2_624_331,), jnp.float32),
        idx, idx, idx, c_cap=1 << 23).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_gather_executor(topo):
    """The sharded gather-SpGEMM program over the four chips of a v5e:2x2."""
    from repro.runtime.shard import _gather_shard_fn
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    pp = 1 << 23
    idx = arg((4, pp), jnp.int64, P("data"))
    compiled = _gather_shard_fn(mesh).lower(
        arg((4, 1 << 19), jnp.float32, P("data")),
        arg((1_640_000,), jnp.float32, P()), idx, idx, idx,
        c_cap=1 << 23).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_cholesky_level_step(one_chip, dtype):
    """One etree level of the C3 factor (L nnz ~860k, 4096 cmod triples)."""
    n_vals, bu, bc, bo = 860_001, 4096, 1, 128
    i = lambda n: one_chip((n,), jnp.int64)      # noqa: E731
    _level_step.lower(one_chip((n_vals,), dtype), i(bu), i(bu), i(bu),
                      i(bc), i(bo), i(bo)).compile()
