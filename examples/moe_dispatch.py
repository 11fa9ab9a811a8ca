"""RIR-bundled MoE dispatch: the paper's technique inside an LM layer.

Shows the full path: router → capacity bundling (RIR discipline: fixed
shapes, padding, overflow accounting) → grouped expert GEMM, on both the
jnp lowering path and the Pallas ``moe_gemm`` kernel (scalar-prefetch
expert routing), validated against each other.

    PYTHONPATH=src python examples/moe_dispatch.py
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.launch.compile_cache import init_compile_cache
from repro.models.moe import expert_capacity, route_and_bundle, unbundle

init_compile_cache()

T, D, E, K = 512, 128, 8, 2
key = jax.random.PRNGKey(0)
k1, k2, k3 = jax.random.split(key, 3)
tokens = jax.random.normal(k1, (T, D), jnp.float32)
router_w = jax.random.normal(k2, (D, E), jnp.float32) * 0.02
w_expert = jax.random.normal(k3, (E, D, D), jnp.float32) / np.sqrt(D)

cap = expert_capacity(T, E, K, capacity_factor=1.25)
print(f"{T} tokens × top-{K} over {E} experts → bundles of capacity {cap} "
      f"({E * cap} slots for {T * K} assignments)")

# 1. the irregular part — routing — becomes regular RIR bundles
x_bundles, combine, aux_loss, dropped = route_and_bundle(
    tokens, router_w, n_experts=E, top_k=K, capacity=cap)
print(f"bundled: {x_bundles.shape}; dropped (overflow) = {dropped:.2%}; "
      f"load-balance aux = {float(aux_loss):.3f}")

# 2. the regular part — grouped GEMM — streams through the MXU
bundle_expert = jnp.arange(E, dtype=jnp.int32)
y_kernel = ops.moe_gemm(x_bundles, w_expert, bundle_expert, bk=128, bf=128)
y_ref = ref.moe_gemm_ref(x_bundles, w_expert, bundle_expert)
np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_ref),
                           rtol=1e-3, atol=1e-3)
print("Pallas kernel == jnp oracle ✓")

# 3. un-bundle back to token order with gate mixing
out = unbundle(jnp.asarray(y_ref), combine, D)
print(f"output: {out.shape}; finite: {bool(jnp.isfinite(out).all())} ✓")

# 4. repeated routings hit the plan cache: the assignment *pattern* is
#    fingerprinted under the moe_dispatch op tag, so a sticky router (decode
#    steps, replayed traces) pays the bundling plan once
from repro.models.moe import host_route
from repro.runtime import ReapRuntime

rt = ReapRuntime()
expert_ids, gates = host_route(tokens, router_w, top_k=K)
xb, plan, st_cold = rt.moe_dispatch(np.asarray(tokens), expert_ids,
                                    n_experts=E, capacity=cap)
xb2, plan2, st_warm = rt.moe_dispatch(np.asarray(tokens) * 0.5, expert_ids,
                                      n_experts=E, capacity=cap)
y_warm = ops.moe_gemm_schedule(plan.schedule, jnp.asarray(xb2, jnp.float32),
                               w_expert, bk=128, bf=128)
mixed = plan.combine(np.asarray(y_warm), gates)
print(f"plan cache: cold hit={st_cold['cache_hit']}, "
      f"warm hit={st_warm['cache_hit']}; combined output {mixed.shape} ✓")
