"""Sharding rules + distributed train/serve steps on a small mesh."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from repro import compat
from jax.sharding import PartitionSpec as P

from repro.configs import LM_ARCHS, get_config
from repro.lm import model as M
from repro.parallel import sharding as SH
from repro.parallel.zero import zero_upgrade
from repro.train import optim as optim_lib

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 placeholder devices")


@pytest.fixture(scope="module")
def mesh():
    return compat.make_mesh((2, 4), ("data", "model"))


def _axis_size(mesh, e):
    import numpy as _np
    if e is None:
        return 1
    if isinstance(e, tuple):
        return int(_np.prod([mesh.shape[a] for a in e]))
    return mesh.shape[e]


def _assert_valid(tree, specs, mesh):
    def check(leaf, spec):
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for d, e in zip(leaf.shape, entries):
            assert d % _axis_size(mesh, e) == 0, (leaf.shape, spec)
    jax.tree.map(check, tree, specs,
                 is_leaf=lambda x: isinstance(x, P))


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_param_and_cache_specs_divide(arch, mesh):
    cfg = get_config(arch, smoke=True)
    aparams = M.abstract_params(cfg)
    _assert_valid(aparams, SH.param_specs(aparams, cfg, mesh), mesh)
    acache = M.abstract_cache(cfg, batch=8, max_len=32)
    _assert_valid(acache, SH.cache_specs(acache, cfg, mesh), mesh)


@pytest.mark.parametrize("arch", ["glm4_9b", "grok_1_314b"])
def test_sharded_train_matches_single_device(arch, mesh):
    """3 sharded training steps == 3 single-device steps (same math)."""
    cfg = dataclasses.replace(get_config(arch, smoke=True), seq_shard=True)
    if cfg.moe is not None:
        # generous capacity so distributed dispatch drops nothing
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    opt = optim_lib.adafactor(1e-3)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                          cfg.vocab)}

    def run(mesh_or_none):
        state = {"params": params, "opt": opt.init(params),
                 "step": jnp.zeros((), jnp.int32)}
        step = M.make_train_step(cfg, mesh_or_none, opt)
        if mesh_or_none is None:
            jstep = jax.jit(step)
            for _ in range(3):
                state, m = jstep(state, batch)
            return m["loss"]
        pspecs = SH.param_specs(jax.eval_shape(lambda: params), cfg, mesh)
        sspecs = {"params": pspecs,
                  "opt": SH.opt_state_specs(
                      pspecs, jax.eval_shape(lambda: state["opt"]), mesh),
                  "step": P()}
        with jax.set_mesh(mesh):
            st = jax.device_put(state, SH.shardings(sspecs, mesh))
            jstep = jax.jit(step, in_shardings=(SH.shardings(sspecs, mesh),
                                                SH.shardings(SH.batch_specs(
                                                    jax.eval_shape(lambda: batch),
                                                    cfg, mesh), mesh)),
                            out_shardings=(SH.shardings(sspecs, mesh), None))
            b = jax.device_put(batch, SH.shardings(SH.batch_specs(
                jax.eval_shape(lambda: batch), cfg, mesh), mesh))
            for _ in range(3):
                st, m = jstep(st, b)
            return m["loss"]

    l_single = float(run(None))
    l_mesh = float(run(mesh))
    # MoE ref (single-dev) vs capacity dispatch can differ slightly via
    # routing ties; dense archs must match tightly.
    tol = 5e-2 if cfg.moe is not None else 5e-4
    assert abs(l_single - l_mesh) <= tol * max(1.0, abs(l_single)), \
        (l_single, l_mesh)


def test_sharded_decode_matches_single_device(mesh):
    cfg = get_config("glm4_9b", smoke=True)
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (8, 9), 0, cfg.vocab)
    last_1, caches_1 = M.prefill(params, {"tokens": tok}, cfg, None,
                                 max_len=16)
    lg_1, _ = M.decode_step(params, caches_1, tok[:, -1:], jnp.int32(8),
                            cfg, None)
    with jax.set_mesh(mesh):
        last_m, caches_m = jax.jit(
            lambda p, b: M.prefill(p, b, cfg, mesh, max_len=16))(
                params, {"tokens": tok})
        lg_m, _ = jax.jit(
            lambda p, c, t: M.decode_step(p, c, t, jnp.int32(8), cfg, mesh))(
                params, caches_m, tok[:, -1:])
    np.testing.assert_allclose(np.asarray(lg_1), np.asarray(lg_m),
                               rtol=2e-4, atol=2e-4)


def test_zero_upgrade_shards_replicated_leaves(mesh):
    specs = {"big": P(None, None), "tiny": P(None)}
    tree = {"big": jax.ShapeDtypeStruct((64, 32), jnp.float32),
            "tiny": jax.ShapeDtypeStruct((3,), jnp.float32)}
    up = zero_upgrade(specs, tree, mesh)
    assert up["big"] != specs["big"]          # got a data axis
    assert up["tiny"] == P(None)              # 3 % 2 != 0 -> untouched


def test_batch_specs_shard_batch_dim(mesh):
    cfg = get_config("qwen2_vl_72b", smoke=True)
    batch = {"embeds": jax.ShapeDtypeStruct((8, 16, cfg.d_model), jnp.float32),
             "labels": jax.ShapeDtypeStruct((8, 16), jnp.int32)}
    specs = SH.batch_specs(batch, cfg, mesh)
    assert specs["embeds"][0] is not None
    assert specs["labels"][0] is not None
