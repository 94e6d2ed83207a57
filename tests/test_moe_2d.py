"""Numerical correctness of the replicated-token 2D expert-parallel MoE path
(the long-context-decode optimization from EXPERIMENTS.md §Perf) against the
single-device reference — run on an 8-device (4 data x 2 model) mesh in a
subprocess."""
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.dist.partitioning import Rules
from repro.launch.mesh import make_debug_mesh
from repro.models import moe as moe_mod

import dataclasses
cfg = get_smoke_config("deepseek-moe-16b")
# drop-free capacity: the reference and sharded paths compute per-expert
# capacity over different token populations (global vs per-shard)
cfg = dataclasses.replace(
    cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=50.0))
key = jax.random.PRNGKey(0)
params_ann = moe_mod.init_moe(key, cfg)
from repro.models.param import split_tree
params, _ = split_tree(params_ann)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 1, cfg.d_model), jnp.float32) * 0.3

# reference: local path (no mesh)
y_ref, _, _ = moe_mod.apply_moe(params, x, cfg, train=False)

# 2D path: mesh (4 data x 2 model), batch axes overridden to None
mesh = make_debug_mesh(4, 2)
rules = Rules.default(mesh).override(acts={"batch": None})
with mesh:
    y_2d, _, _ = jax.jit(lambda p, xx: moe_mod.apply_moe(
        p, xx, cfg, train=False, mesh=mesh, rules=rules))(params, x)
err = float(jnp.abs(y_2d - y_ref).max())
rel = err / float(jnp.abs(y_ref).max())
assert rel < 2e-2, (err, rel)

# standard EP path (batch sharded) must also agree
rules_b = Rules.default(mesh)
with mesh:
    y_ep, _, _ = jax.jit(lambda p, xx: moe_mod.apply_moe(
        p, xx, cfg, train=False, mesh=mesh, rules=rules_b))(
        params, jnp.tile(x, (4, 1, 1)))
y_ref4, _, _ = moe_mod.apply_moe(params, jnp.tile(x, (4, 1, 1)), cfg, train=False)
err2 = float(jnp.abs(y_ep - y_ref4).max())
rel2 = err2 / float(jnp.abs(y_ref4).max())
assert rel2 < 2e-2, (err2, rel2)
print("MOE_2D_OK", rel, rel2)
"""


def test_moe_2d_matches_reference():
    res = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        env={"PYTHONPATH": SRC, "PATH": "/usr/bin:/bin", "HOME": "/root",
             "JAX_PLATFORMS": "cpu"},  # backend probing hangs without it
        capture_output=True, text=True, timeout=420)
    assert "MOE_2D_OK" in res.stdout, (res.stdout[-500:], res.stderr[-2000:])
