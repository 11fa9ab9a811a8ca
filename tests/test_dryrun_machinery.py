"""The dry-run machinery itself, exercised at test scale (8 fake devices,
reduced configs) — lower+compile+cost/memory/collective extraction for one
cell of each step kind."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_script(body: str, timeout=900):
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


COMMON = """
import dataclasses, jax
from repro.configs import get_config, reduced_config, ShapeConfig
from repro.launch.dryrun import _lower_compile, _terms
from repro.launch.mesh import make_mesh
from repro.launch import roofline as R

cfg = dataclasses.replace(reduced_config(get_config("{arch}")),
                          compute_dtype="bfloat16")
shape = ShapeConfig("t", "{kind}", {seq}, {batch})
mesh = make_mesh((4, 2), ("data", "model"))
lowered, compiled = _lower_compile(cfg, shape, mesh)
t = _terms(compiled)
assert t["flops"] > 0, t
mem = compiled.memory_analysis()
assert mem.argument_size_in_bytes > 0
terms = R.roofline_terms({{"flops": t["flops"], "bytes accessed": t["bytes"]}},
                         R.CollectiveStats({{}}, t["coll"], t["coll_count"], []),
                         8)
assert terms["dominant"] in ("compute", "memory", "collective")
print("OK", "{arch}", "{kind}", t["coll_count"], "collectives,",
      f"{{t['flops']:.3g}}", "flops/dev")
"""


def test_train_cell_lowers_on_small_mesh():
    out = run_script(COMMON.format(arch="qwen3-1.7b", kind="train",
                                   seq=64, batch=8))
    assert "OK qwen3-1.7b train" in out


def test_decode_cell_lowers_on_small_mesh():
    out = run_script(COMMON.format(arch="gemma2-2b", kind="decode",
                                   seq=64, batch=8))
    assert "OK gemma2-2b decode" in out


def test_prefill_cell_lowers_on_small_mesh():
    out = run_script(COMMON.format(arch="rwkv6-1.6b", kind="prefill",
                                   seq=64, batch=8))
    assert "OK rwkv6-1.6b prefill" in out


def test_moe_cell_has_ep_collectives():
    out = run_script(COMMON.format(arch="dbrx-132b", kind="train",
                                   seq=64, batch=8))
    assert "OK dbrx-132b train" in out
