"""MoE dispatch: distributed implementations vs the dense reference."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.configs import get_config
from repro.lm import moe as moe_lib
from repro.lm.config import ArchConfig, MoEConfig

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 placeholder devices")


@pytest.fixture(scope="module")
def mesh():
    return compat.make_mesh((2, 4), ("data", "model"))


def _setup(num_experts=8, top_k=2, d=64, f=96, B=4, S=16, cf=8.0, impl="auto"):
    cfg = dataclasses.replace(
        get_config("kimi_k2_1t_a32b", smoke=True),
        d_model=d,
        moe=MoEConfig(num_experts=num_experts, top_k=top_k, d_ff_expert=f,
                      capacity_factor=cf, impl=impl))
    key = jax.random.PRNGKey(0)
    p = {
        "router": jax.random.normal(key, (d, num_experts), jnp.float32) * 0.1,
        "w_gate": jax.random.normal(jax.random.fold_in(key, 1),
                                    (num_experts, d, f)) * 0.05,
        "w_up": jax.random.normal(jax.random.fold_in(key, 2),
                                  (num_experts, d, f)) * 0.05,
        "w_down": jax.random.normal(jax.random.fold_in(key, 3),
                                    (num_experts, f, d)) * 0.05,
    }
    x = jax.random.normal(jax.random.fold_in(key, 4), (B, S, d))
    return cfg, p, x


@pytest.mark.parametrize("impl", ["ep_psum", "ep_a2a", "tp"])
def test_distributed_matches_ref_generous_capacity(mesh, impl):
    cfg, p, x = _setup(impl=impl)
    y_ref, _ = moe_lib._moe_ref(x, p, cfg)
    with jax.set_mesh(mesh):
        y, aux = jax.jit(lambda x, p: moe_lib.moe_ffn(x, p, cfg, mesh))(x, p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)
    assert bool(jnp.isfinite(aux))


def test_capacity_drops_bounded(mesh):
    """At capacity_factor 1.0 some tokens drop; outputs stay close to ref
    in aggregate (relative Frobenius error bounded)."""
    cfg, p, x = _setup(cf=1.0, impl="ep_psum")
    y_ref, _ = moe_lib._moe_ref(x, p, cfg)
    with jax.set_mesh(mesh):
        y, _ = jax.jit(lambda x, p: moe_lib.moe_ffn(x, p, cfg, mesh))(x, p)
    rel = float(jnp.linalg.norm(y - y_ref) / jnp.linalg.norm(y_ref))
    assert rel < 0.6, rel
    assert bool(jnp.isfinite(y).all())


def test_pick_impl_rules(mesh):
    cfg_big, _, _ = _setup(num_experts=8)      # 8 % 4 == 0 -> ep
    assert moe_lib.pick_impl(cfg_big, mesh, decode=False) == "ep_a2a"
    assert moe_lib.pick_impl(cfg_big, mesh, decode=True) == "ep_psum"
    cfg_small, _, _ = _setup(num_experts=6)    # 6 % 4 != 0 -> tp
    assert moe_lib.pick_impl(cfg_small, mesh, decode=False) == "tp"
    assert moe_lib.pick_impl(cfg_big, None, decode=False) == "ref"


def test_grads_flow_through_dispatch(mesh):
    """Router + expert weights receive nonzero gradients through the
    sort/scatter dispatch (ep_a2a)."""
    cfg, p, x = _setup(impl="ep_a2a")

    def loss(p):
        y, aux = moe_lib.moe_ffn(x, p, cfg, mesh)
        return jnp.sum(y ** 2) + 0.01 * aux

    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(loss))(p)
    for k, v in g.items():
        assert bool(jnp.isfinite(v).all()), k
        assert float(jnp.abs(v).max()) > 0.0, k


def test_aux_loss_prefers_balance():
    probs_bal = jnp.full((64, 4), 0.25)
    idx_bal = jnp.stack([jnp.arange(64) % 4, (jnp.arange(64) + 1) % 4], -1)
    probs_skew = jnp.tile(jnp.asarray([[0.97, 0.01, 0.01, 0.01]]), (64, 1))
    idx_skew = jnp.zeros((64, 2), jnp.int32)
    bal = moe_lib.router_aux_loss(probs_bal, idx_bal, 4)
    skew = moe_lib.router_aux_loss(probs_skew, idx_skew, 4)
    assert float(bal) < float(skew)
