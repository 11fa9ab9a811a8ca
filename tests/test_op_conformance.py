"""Registry-wide conformance suite: every op in ``list_ops()`` gets the
same battery, parametrized from the shared example table
(``repro.analysis.op_examples``).  Admitting a future op via
``register_op`` + one ``OpExample`` entry buys this coverage for free:

* **coverage** — every non-router op has an example (a registered op the
  harness can't drive is a silent hole, reported as a failure);
* **plan purity** — perturbed values → same fingerprint, bit-identical
  serialized plan (the dynamic REAP001 proof, via ``check_op_purity``);
* **serialize round-trip** — plan → flat dict → plan → flat dict is
  bit-stable (what the persistent store relies on);
* **cache + store round-trip** — a second same-pattern call hits the
  in-memory cache; a *fresh runtime* sharing the store_dir answers from
  disk (per-op ``store_hits``) and computes the same result;
* **one chunk vs four** — where ``execute_chunked`` exists, the pipeline
  at one chunk (run inline) matches it at four (overlapped) numerically;
* **capability honesty** — declared capability metadata is well-formed
  and the derived ``chunked`` flag matches the registered hooks.
"""
import dataclasses

import numpy as np
import pytest

from repro.analysis.op_examples import builtin_examples
from repro.analysis.purity_check import (_payload_diff, _plan_payload,
                                         check_op_purity)
from repro.core import CSR
from repro.runtime import ReapRuntime
from repro.runtime import ops as _ops
from repro.runtime.api import RuntimeConfig

N = 256
ALL_TAGS = _ops.list_ops()
CONCRETE = [t for t in ALL_TAGS if _ops.get_op(t).route is None]
CHUNKED = [t for t in CONCRETE if _ops.get_op(t).execute_chunked is not None]
SHARDABLE = [t for t in CONCRETE if _ops.get_op(t).capabilities.shardable]
EXAMPLES = builtin_examples(N)


def _example(tag):
    ex = EXAMPLES.get(tag)
    assert ex is not None, (
        f"op {tag!r} is registered but has no entry in "
        "analysis/op_examples.py — conformance cannot drive it "
        "(coverage gap)")
    return ex


def _runtime(tag, **extra):
    ex = _example(tag)
    kw = dict(n_chunks=1)
    kw.update(ex.runtime_kw)
    kw.update(extra)
    return ReapRuntime(**kw)


def _arrays(result):
    """Every dense ndarray reachable in an op result (CSR → dense)."""
    if isinstance(result, CSR):
        return [result.to_dense()]
    if isinstance(result, np.ndarray):
        return [result]
    if isinstance(result, (tuple, list)):
        return [a for r in result for a in _arrays(r)]
    if hasattr(result, "__array__"):              # jax arrays
        return [np.asarray(result)]
    return []                                     # plans/stats: not values


def test_registry_has_expected_ops():
    """≥ 8 ops after this PR, the two new admissions among them."""
    assert len(ALL_TAGS) >= 8, ALL_TAGS
    for tag in ("spgemm", "spgemm_gather", "spgemm_block", "cholesky",
                "moe_dispatch", "spmm", "block_attention", "spmv"):
        assert tag in ALL_TAGS, ALL_TAGS


@pytest.mark.parametrize("tag", ALL_TAGS)
def test_example_coverage(tag):
    if _ops.get_op(tag).route is not None:
        pytest.skip("router: plans belong to its targets")
    _example(tag)


@pytest.mark.parametrize("tag", ALL_TAGS)
def test_capabilities_well_formed(tag):
    spec = _ops.get_op(tag)
    summary = _ops.capability_summary(spec)
    assert summary["routing"] in _ops.CAPABILITY_ROUTINGS
    assert summary["dtypes"], summary
    assert all(isinstance(d, str) for d in summary["dtypes"])
    assert summary["chunked"] == (spec.execute_chunked is not None)


@pytest.mark.parametrize("tag", CONCRETE)
def test_plan_purity(tag):
    res = check_op_purity(tag, n=N)
    assert res["ok"], res["detail"]


@pytest.mark.parametrize("tag", CONCRETE)
def test_serialize_round_trip(tag):
    """plan → payload → plan → payload is bit-stable (store contract)."""
    spec = _ops.get_op(tag)
    ex = _example(tag)
    cfg = RuntimeConfig(n_chunks=1, **ex.runtime_kw)
    fp, payload0 = _plan_payload(spec, ex.operands(0), cfg, ex.kw)
    plan1 = _ops.deserializer_for(fp.op)(payload0)
    assert dataclasses.is_dataclass(plan1), type(plan1)
    payload1 = _ops.serializer_for(fp.op)(plan1)
    diff = _payload_diff(payload0, payload1)
    assert diff is None, diff


@pytest.mark.parametrize("tag", CONCRETE)
def test_cache_hit_and_store_round_trip(tag, tmp_path):
    ex = _example(tag)
    store = str(tmp_path / "plans")

    rt = _runtime(tag, store_dir=store)
    r0, s0 = rt.run(tag, *ex.operands(0), **ex.kw)
    r1, s1 = rt.run(tag, *ex.operands(0), **ex.kw)
    assert not s0["cache_hit"], "first same-pattern call must be a miss"
    assert s1["cache_hit"], "second same-pattern call must hit the cache"
    per_op = rt.cache_stats()["per_op"][tag]
    assert per_op["misses"] == 1 and per_op["hits"] == 1, per_op

    # identical values → identical results on the warm path
    a0, a1 = _arrays(r0), _arrays(r1)
    assert a0 and len(a0) == len(a1)
    for x0, x1 in zip(a0, a1):
        np.testing.assert_allclose(x0, x1, rtol=1e-5, atol=1e-5)

    # a fresh runtime sharing the store answers from disk, same numbers
    rt2 = _runtime(tag, store_dir=store)
    r2, s2 = rt2.run(tag, *ex.operands(0), **ex.kw)
    per_op2 = rt2.cache_stats()["per_op"][tag]
    assert per_op2["store_hits"] == 1, per_op2
    for x0, x2 in zip(a0, _arrays(r2)):
        np.testing.assert_allclose(x0, x2, rtol=1e-5, atol=1e-5)


def test_expected_ops_are_shardable():
    """The three data-parallel ops of this PR admit sharding; declarations
    and hooks agree registry-wide (the OpSpec parity check, re-proven from
    the outside)."""
    assert set(SHARDABLE) >= {"spgemm_gather", "spmm", "moe_dispatch"}
    for tag in CONCRETE:
        spec = _ops.get_op(tag)
        assert (spec.shard_plan is not None) == spec.capabilities.shardable


def _data_mesh():
    import jax
    from repro.launch.mesh import make_mesh
    return len(jax.devices()), make_mesh((len(jax.devices()),), ("data",))


@pytest.mark.parametrize("tag", SHARDABLE)
def test_sharded_vs_single_host_bit_for_bit(tag):
    """Row-range/expert sharding must be bit-for-bit the single-host
    result — not allclose — for every shardable op, cold and warm.  On the
    dev box the data mesh is however many host devices exist (often 1);
    tier1.yml reruns this battery under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the 8-way
    split is exercised in CI."""
    n_dev, mesh = _data_mesh()
    ex = _example(tag)
    r0, _ = _runtime(tag).run(tag, *ex.operands(0), **ex.kw)
    rt = _runtime(tag)
    r1, s1 = rt.run(tag, *ex.operands(0), mesh=mesh, **ex.kw)
    assert not s1["cache_hit"]
    assert s1["n_shards"] == n_dev
    a0, a1 = _arrays(r0), _arrays(r1)
    assert a0 and len(a0) == len(a1)
    for x0, x1 in zip(a0, a1):
        np.testing.assert_array_equal(x0, x1)

    # warm sharded call: the shard artifact round-trips the cache keyed by
    # (fingerprint, shards) and reproduces the same bits
    r2, s2 = rt.run(tag, *ex.operands(0), mesh=mesh, **ex.kw)
    assert s2["cache_hit"]
    for x0, x2 in zip(a0, _arrays(r2)):
        np.testing.assert_array_equal(x0, x2)


@pytest.mark.parametrize("tag", SHARDABLE)
def test_sharded_store_round_trip(tag, tmp_path):
    """A fresh runtime sharing the plan store answers the *sharded* call
    from disk (ShardedPlan payloads deserialize in any process) and still
    matches the single-host result exactly."""
    n_dev, mesh = _data_mesh()
    ex = _example(tag)
    store = str(tmp_path / "plans")
    rt = _runtime(tag, store_dir=store)
    r1, _ = rt.run(tag, *ex.operands(0), mesh=mesh, **ex.kw)

    rt2 = _runtime(tag, store_dir=store)
    r2, s2 = rt2.run(tag, *ex.operands(0), mesh=mesh, **ex.kw)
    assert s2["cache_hit"] and s2["store_hit"], dict(s2)
    for x1, x2 in zip(_arrays(r1), _arrays(r2)):
        np.testing.assert_array_equal(x1, x2)


@pytest.mark.parametrize("tag", CHUNKED)
def test_chunked_vs_sync_equivalence(tag):
    ex = _example(tag)
    sync_rt = _runtime(tag, n_chunks=1)
    chunk_rt = _runtime(tag, n_chunks=4)
    r_sync, s_sync = sync_rt.run(tag, *ex.operands(3), **ex.kw)
    r_chunk, s_chunk = chunk_rt.run(tag, *ex.operands(3), **ex.kw)
    a_sync, a_chunk = _arrays(r_sync), _arrays(r_chunk)
    assert a_sync and len(a_sync) == len(a_chunk)
    for x0, x1 in zip(a_sync, a_chunk):
        np.testing.assert_allclose(x0, x1, rtol=1e-4, atol=1e-4)
