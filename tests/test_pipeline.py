"""GPipe pipeline executor vs sequential reference (exact equality)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.parallel.pipeline import gpipe

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 placeholder devices")


def _block(lp, x):
    return jnp.tanh(x @ lp["w"] + lp["b"])


def _seq(params, x):
    def one(h, lp):
        return _block(lp, h), None
    h, _ = jax.lax.scan(one, x, params)
    return h


@pytest.mark.parametrize("stages,n_micro", [(4, 6), (4, 4), (2, 3)])
def test_gpipe_matches_sequential(stages, n_micro):
    mesh = compat.make_mesh((stages, 8 // stages), ("pod", "data"))
    L, D, mb = 2 * stages, 16, 4
    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (L, D, D)) * 0.3,
              "b": jnp.zeros((L, D))}
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, D))
    ref = jax.vmap(lambda xm: _seq(params, xm))(x)
    with jax.set_mesh(mesh):
        out = jax.jit(gpipe(_block, mesh, axis="pod"))(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_gpipe_differentiable():
    mesh = compat.make_mesh((4, 2), ("pod", "data"))
    L, D = 4, 8
    params = {"w": jax.random.normal(jax.random.PRNGKey(0), (L, D, D)) * 0.3,
              "b": jnp.zeros((L, D))}
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 2, D))

    def loss_pp(p):
        return jnp.sum(gpipe(_block, mesh, axis="pod")(p, x) ** 2)

    def loss_seq(p):
        return jnp.sum(jax.vmap(lambda xm: _seq(p, xm))(x) ** 2)

    with jax.set_mesh(mesh):
        g_pp = jax.jit(jax.grad(loss_pp))(params)
    g_seq = jax.grad(loss_seq)(params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5), g_pp, g_seq)
