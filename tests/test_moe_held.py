"""An expert layer that holds a share of the routed experts.

``MoEConfig.held_experts`` gives the layer one chip's share of an
expert-parallel deployment: the router scores all E experts, and only the
held ones compute.  Four disjoint shares, each with the shared experts left
out, plus the shared experts once, add up to the uncut layer.  A share of
experts other than the first is the first share of a model whose experts
are renumbered so that they come first."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import get_smoke_config
from repro.configs.base import MoEConfig
from repro.dist.partitioning import Rules
from repro.models import moe as moe_mod
from repro.models.param import split_tree

SHARES = 4


def _cfg(norm_topk: bool, held: int = 0):
    cfg = get_smoke_config("deepseek-moe-16b")
    moe = dataclasses.replace(cfg.moe, n_shared_experts=2, norm_topk=norm_topk,
                              held_experts=held)
    assert (moe.n_routed_experts, moe.top_k) == (8, 2)
    return dataclasses.replace(cfg, dtype="float32", moe=moe)


def _share(params, first: int, held: int):
    """Experts first .. first+held-1 renumbered to 0 .. held-1 (the router's
    columns rolled alike), only they kept; no shared experts."""
    out = {"router": jnp.roll(params["router"], -first, axis=1)}
    for w in ("w_gate", "w_up", "w_down"):
        out[w] = params[w][first:first + held]
    return out


@pytest.mark.parametrize("norm_topk", [True, False])
def test_held_shares_add_up_to_the_uncut_layer(norm_topk):
    cfg = _cfg(norm_topk)
    params, _ = split_tree(moe_mod.init_moe(jax.random.PRNGKey(3), cfg))
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, cfg.d_model), jnp.float32)
    whole, _, _ = moe_mod.apply_moe(params, x, cfg, train=False)

    held = cfg.moe.n_routed_experts // SHARES
    cut = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, n_shared_experts=0,
                                     held_experts=held))
    routed = sum(moe_mod.apply_moe(_share(params, j * held, held), x, cut,
                                   train=False)[0] for j in range(SHARES))
    shared = moe_mod._shared_ffn(x, params["sh_gate"], params["sh_up"],
                                 params["sh_down"], cfg.dtype)
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # every share's layer, built by the program, keeps the E-wide router
    share, _ = split_tree(moe_mod.init_moe(jax.random.PRNGKey(3),
                                           _cfg(norm_topk, held)))
    assert share["router"].shape == (cfg.d_model, cfg.moe.n_routed_experts)
    assert share["w_gate"].shape[0] == share["w_down"].shape[0] == held
    assert share["sh_gate"].shape == params["sh_gate"].shape


def test_counts_of_a_held_share():
    cfg = _cfg(norm_topk=False, held=3)
    params, _ = split_tree(moe_mod.init_moe(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 7, cfg.d_model), jnp.float32)
    _, _, counts = moe_mod.apply_moe(params, x, cfg, train=False)
    ids, _, _ = moe_mod._route(params, x, cfg, train=False)
    # dropless inference: every pair on a held expert is computed, in a
    # buffer of capacity = all 14 tokens for each of the 3 held experts
    assert counts.tolist() == [int((ids < 3).sum()), 3 * 14]
    assert 0 < counts[0] < counts[1]


@pytest.mark.parametrize("held", [9, -1])
def test_held_experts_outside_the_router_is_refused(held):
    with pytest.raises(ValueError, match="held_experts"):
        MoEConfig(n_routed_experts=8, top_k=2, expert_d_ff=64,
                  held_experts=held)


def test_held_experts_that_do_not_split_over_the_mesh_are_refused():
    cfg = _cfg(norm_topk=False, held=6)
    params, _ = split_tree(moe_mod.init_moe(jax.random.PRNGKey(0), cfg))
    mesh = AbstractMesh((1, 4), ("data", "model"))
    rules = Rules(mesh=mesh, axis_sizes={"data": 1, "model": 4}, params={},
                  acts={"batch": ("data",)})
    x = jnp.zeros((1, 2, cfg.d_model), jnp.float32)
    with pytest.raises(ValueError, match="6 held experts do not split"):
        moe_mod.apply_moe(params, x, cfg, train=False, mesh=mesh, rules=rules)
