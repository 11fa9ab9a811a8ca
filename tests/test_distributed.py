"""Multi-device semantics tests.

Each test runs a small script in a SUBPROCESS with
``--xla_force_host_platform_device_count=8`` so the main pytest process
keeps its single real device (per the dry-run protocol).  Scripts verify:

  * sharded train step == single-device train step (bitwise-ish)
  * checkpoint saved on mesh A restores (resharded) onto smaller mesh B
  * int8 EF cross-pod compression step trains and stays close to exact
  * pipeline-parallel stage execution == sequential reference
"""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_script(body: str, timeout=600):
    script = "import os\n" \
        "os.environ['XLA_FLAGS'] = " \
        "'--xla_force_host_platform_device_count=8'\n" + textwrap.dedent(body)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_sharded_train_step_matches_single_device():
    run_script("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced_config
    from repro.models import model as M
    from repro.optim import adamw
    from repro.launch.steps import make_train_step
    from repro.launch.mesh import make_mesh
    from repro.parallel import sharding as S

    cfg = reduced_config(get_config("qwen3-1.7b"))
    opt_cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=0, total_steps=10)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw.init(opt_cfg, params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)))}

    # single device reference
    ref_step = jax.jit(make_train_step(cfg, opt_cfg))
    p1, o1, m1 = ref_step(params, opt, batch)

    mesh = make_mesh((4, 2), ("data", "model"))
    psh = S.params_shardings(cfg, mesh)
    osh = {"m": psh, "v": psh, "step": jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec())}
    step = jax.jit(make_train_step(cfg, opt_cfg, mesh),
                   in_shardings=(psh, osh, None), out_shardings=(psh, osh, None))
    p2, o2, m2 = step(jax.device_put(params, psh), jax.device_put(opt, osh), batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3, (m1, m2)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-3)
    print("OK sharded==single")
    """)


def test_checkpoint_reshard_elastic():
    run_script("""
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from repro.configs import get_config, reduced_config
    from repro.models import model as M
    from repro.checkpoint import manager as ckpt
    from repro.launch.mesh import make_mesh
    from repro.parallel import sharding as S

    cfg = reduced_config(get_config("gemma2-2b"))
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    mesh_a = make_mesh((4, 2), ("data", "model"))
    psh_a = S.params_shardings(cfg, mesh_a)
    sharded = jax.device_put(params, psh_a)
    d = tempfile.mkdtemp()
    ckpt.save(d, 3, {"params": sharded})

    # "node failure": restart on 3/4 of the data axis
    mesh_b = make_mesh((3, 2), ("data", "model"))
    psh_b = S.params_shardings(cfg, mesh_b)
    restored, manifest = ckpt.restore(d, {"params": params},
                                      shardings={"params": psh_b})
    assert manifest["step"] == 3
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK elastic reshard")
    """)


def test_compressed_cross_pod_step():
    run_script("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config, reduced_config
    from repro.models import model as M
    from repro.optim import adamw
    from repro.parallel.compression import (make_compressed_train_step,
                                            init_error_state)
    from repro.launch.steps import make_train_step
    from repro.launch.mesh import make_mesh

    cfg = reduced_config(get_config("qwen3-1.7b"))
    opt_cfg = adamw.AdamWConfig(lr=1e-2, warmup_steps=0, total_steps=10)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw.init(opt_cfg, params)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32))),
             "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (8, 32)))}

    ref_step = jax.jit(make_train_step(cfg, opt_cfg))
    p_ref, _, m_ref = ref_step(params, opt, batch)

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    err = init_error_state(params)
    with mesh:
        step = jax.jit(make_compressed_train_step(cfg, opt_cfg, mesh))
        p_c, o_c, err, m_c = step(params, opt, err, batch)
    # int8-compressed grads → params close to exact step
    assert abs(float(m_ref["loss"]) - float(m_c["loss"])) < 1e-3
    deltas = []
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_c)):
        deltas.append(float(np.max(np.abs(np.asarray(a, np.float32)
                                          - np.asarray(b, np.float32)))))
    assert max(deltas) < 5e-2, max(deltas)
    print("OK compressed step, max param delta", max(deltas))
    """)


def test_pipeline_parallel_matches_sequential():
    run_script("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_mesh
    from repro.parallel.pipeline import pipeline_apply

    mesh = make_mesh((4, 2), ("pipe", "model"))
    n_stage, n_micro, mb, d = 4, 8, 2, 16
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (n_stage, d, d)) / d ** 0.5
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"])

    with mesh:
        out = pipeline_apply(stage_fn, {"w": w}, x, mesh=mesh, axis="pipe")

    # sequential reference
    ref = x
    for s in range(n_stage):
        ref = jnp.tanh(ref @ w[s])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    print("OK pipeline == sequential")
    """)


def test_production_mesh_shapes():
    run_script("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=512'
    from repro.launch.mesh import make_production_mesh
    m1 = make_production_mesh()
    assert m1.devices.shape == (16, 16) and m1.axis_names == ("data", "model")
    m2 = make_production_mesh(multi_pod=True)
    assert m2.devices.shape == (2, 16, 16)
    assert m2.axis_names == ("pod", "data", "model")
    print("OK meshes")
    """)
