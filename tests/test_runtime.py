"""Fault tolerance: checkpoint/restart determinism, elastic re-sharding,
straggler detection/mitigation, gradient compression convergence."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.runtime.restart import FaultInjected, RestartableRun
from repro.runtime.straggler import MitigationPolicy, StragglerMonitor
from repro.train import checkpoint as ckpt_lib
from repro.train import compression, optim as optim_lib


# ---------------------------------------------------------------------------
# Checkpointing.
# ---------------------------------------------------------------------------


def _tiny_state(key=0):
    k = jax.random.PRNGKey(key)
    return {"params": {"w": jax.random.normal(k, (8, 4)),
                       "b": jnp.zeros((4,))},
            "step": jnp.int32(7)}


def test_checkpoint_roundtrip(tmp_path):
    state = _tiny_state()
    ckpt_lib.save(str(tmp_path), 7, state, extra={"note": "x"})
    assert ckpt_lib.latest_step(str(tmp_path)) == 7
    restored, extra = ckpt_lib.restore(str(tmp_path), 7, state)
    assert extra == {"note": "x"}
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), state, restored)


def test_checkpoint_atomicity_tmp_never_latest(tmp_path):
    state = _tiny_state()
    ckpt_lib.save(str(tmp_path), 3, state)
    os.makedirs(tmp_path / "step_0000000009.tmp")      # simulated crash
    assert ckpt_lib.latest_step(str(tmp_path)) == 3


def test_manager_keeps_last_k(tmp_path):
    m = ckpt_lib.CheckpointManager(str(tmp_path), keep=2)
    state = _tiny_state()
    for s in (1, 2, 3, 4):
        m.save(s, state)
    m.wait()
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path)
                   if d.startswith("step_") and not d.endswith(".tmp"))
    assert steps == [3, 4]


def test_restart_bit_identical(tmp_path):
    """Fault at an arbitrary step, resume, final state == uninterrupted."""
    opt = optim_lib.adam(1e-2)
    params0 = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}

    def batch_fn(step):
        k = jax.random.PRNGKey(step)
        return jax.random.normal(k, (8, 4))

    @jax.jit
    def step_fn(state, x):
        def loss(p):
            return jnp.mean((x @ p["w"] + p["b"]) ** 2)
        g = jax.grad(loss)(state["params"])
        upd, opt_s = opt.update(g, state["opt"], state["params"])
        return {"params": optim_lib.apply_updates(state["params"], upd),
                "opt": opt_s}, None

    def fresh():
        return {"params": params0, "opt": opt.init(params0)}

    ref_dir = tmp_path / "ref"
    run = RestartableRun(step_fn, batch_fn, str(ref_dir), ckpt_every=4)
    ref_state, _ = run.run(fresh(), steps=17)

    crash_dir = tmp_path / "crash"
    run2 = RestartableRun(step_fn, batch_fn, str(crash_dir), ckpt_every=4)
    with pytest.raises(FaultInjected):
        run2.run(fresh(), steps=17, fault_at=9)
    resumed, _ = run2.run(fresh(), steps=17)           # restart from ckpt 8

    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), ref_state, resumed)


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_elastic_reshard_across_meshes(tmp_path):
    """Save sharded on a 2x4 mesh, restore onto 4x2 and 1x8 — identical."""
    mesh_a = compat.make_mesh((2, 4), ("data", "model"))
    mesh_b = compat.make_mesh((4, 2), ("data", "model"))
    w = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    wa = jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))
    ckpt_lib.save(str(tmp_path), 1, {"w": wa})
    shapes = {"w": jax.ShapeDtypeStruct((16, 8), jnp.float32)}
    for mesh, spec in ((mesh_b, P("model", "data")),
                       (mesh_b, P(("data", "model"), None))):
        restored, _ = ckpt_lib.restore_resharded(
            str(tmp_path), 1, shapes,
            {"w": NamedSharding(mesh, spec)})
        np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))


# ---------------------------------------------------------------------------
# Stragglers.
# ---------------------------------------------------------------------------


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(threshold=4.0, warmup=1)
    flagged = [mon.record(i, 0.1 + 0.001 * (i % 3)) for i in range(30)]
    assert not any(flagged[2:])
    assert mon.record(31, 1.0) is True


def test_straggler_stats_true_even_median():
    """Regression: even-length windows used the *upper* middle element
    (``xs[n // 2]``) for both median and MAD, biasing the outlier
    threshold high — a real straggler could hide under the inflated
    median.  The true even-n median is the mean of the middle two."""
    mon = StragglerMonitor(warmup=0)
    for i, dt in enumerate((0.1, 0.2, 0.3, 0.4)):
        mon.record(i, dt)
    med, mad = mon._stats()
    assert med == pytest.approx(0.25)          # not the biased 0.3
    # deviations from 0.25: [0.15, 0.05, 0.05, 0.15] -> median 0.10
    assert mad == pytest.approx(0.10)          # not the biased 0.15


def test_straggler_even_window_catches_formerly_hidden_outlier():
    """With the upper-element median (0.2 over window [0.1, 0.2]) and
    MAD 0.1, a 0.55s step passed as healthy; the true median 0.15 /
    MAD 0.05 flags it."""
    mon = StragglerMonitor(threshold=4.0, warmup=0)
    mon.record(0, 0.1)
    mon.record(1, 0.2)
    assert mon.is_outlier(0.55) is True


def test_mitigation_escalates_and_promotes_spare():
    pol = MitigationPolicy(rebalance_after=2, evict_after=4)
    pol.register_spare("spare-1")
    actions = [pol.report("host-7") for _ in range(4)]
    assert actions[0] == "observe"
    assert actions[1] == "rebalance"
    assert actions[-1] == "evict+promote"
    assert pol.evict("host-7") == "spare-1"
    assert pol.report("host-7") == "observe"           # counter reset


# ---------------------------------------------------------------------------
# Gradient compression (error feedback keeps convergence).
# ---------------------------------------------------------------------------


def test_compression_roundtrip_shapes_and_bound():
    g = jax.random.normal(jax.random.PRNGKey(0), (33, 7)) * 3.0
    payload, meta = compression.compress(g, 4, block=16,
                                         key=jax.random.PRNGKey(1))
    back = compression.decompress(payload, meta, 4)
    assert back.shape == g.shape
    # per-block max error <= scale/levels (stochastic rounding, 1 ulp)
    assert float(jnp.abs(back - g).max()) <= float(jnp.abs(g).max()) / 15 + 1e-5


def test_compressed_sgd_matches_exact_on_quadratic():
    """Error feedback: compressed-gradient SGD converges to the same
    optimum as exact SGD on a strongly convex quadratic."""
    A = jnp.diag(jnp.asarray([1.0, 0.5, 2.0, 0.25]))
    b = jnp.asarray([1.0, -2.0, 0.5, 3.0])
    x_star = jnp.linalg.solve(A, b)

    comp = compression.RadixCompressor(num_steps=4, block=4)

    def grad(x):
        return A @ x - b

    x_exact = jnp.zeros(4)
    x_comp = jnp.zeros(4)
    ef = comp.init(x_comp)
    key = jax.random.PRNGKey(0)
    for i in range(300):
        x_exact = x_exact - 0.3 * grad(x_exact)
        key, k = jax.random.split(key)
        g_hat, ef = comp.roundtrip(grad(x_comp), ef, k)
        x_comp = x_comp - 0.3 * g_hat
    assert float(jnp.linalg.norm(x_exact - x_star)) < 1e-3
    assert float(jnp.linalg.norm(x_comp - x_star)) < 1e-2
    # wire-format ratio at a production block size (the test's block=4 is
    # overhead-dominated on purpose — 4-element toy problem)
    assert compression.RadixCompressor(4, 256).compression_ratio() > 6.0


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_elastic_training_continues_across_topologies(tmp_path):
    """Train on mesh A, checkpoint, reshard to mesh B, keep training:
    the loss curve must continue exactly as an uninterrupted run."""
    import dataclasses as _dc
    from jax.sharding import PartitionSpec as _P
    from repro.configs import get_config
    from repro.launch.mesh import make_test_mesh
    from repro.lm import model as M
    from repro.parallel import sharding as SH

    cfg = get_config("glm4_9b", smoke=True)
    opt = optim_lib.adafactor(1e-2)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                                          cfg.vocab)}

    def make_step(mesh):
        return M.make_train_step(cfg, mesh, opt)

    def place(state, mesh):
        pspecs = SH.param_specs(jax.eval_shape(lambda: state["params"]),
                                cfg, mesh)
        sspecs = {"params": pspecs,
                  "opt": SH.opt_state_specs(
                      pspecs, jax.eval_shape(lambda: state["opt"]), mesh),
                  "step": _P()}
        return jax.device_put(state, SH.shardings(sspecs, mesh))

    params = M.init_params(jax.random.PRNGKey(0), cfg)
    state0 = {"params": params, "opt": opt.init(params),
              "step": jnp.zeros((), jnp.int32)}

    # reference: 4 steps on mesh A only
    mesh_a = make_test_mesh(data=2, model=4)
    with jax.set_mesh(mesh_a):
        st = place(state0, mesh_a)
        step_a = jax.jit(make_step(mesh_a))
        for _ in range(4):
            st, m_ref = step_a(st, batch)
    ref_loss = float(m_ref["loss"])

    # elastic: 2 steps on A -> checkpoint -> restore on B (4x2) -> 2 steps
    with jax.set_mesh(mesh_a):
        st = place(state0, mesh_a)
        for _ in range(2):
            st, _ = step_a(st, batch)
    ckpt_lib.save(str(tmp_path), 2, st)

    mesh_b = make_test_mesh(data=4, model=2)
    with jax.set_mesh(mesh_b):
        st_b = place(jax.tree.map(np.asarray, st), mesh_b)  # structure donor
        restored, _ = ckpt_lib.restore(str(tmp_path), 2, st_b)
        step_b = jax.jit(make_step(mesh_b))
        for _ in range(2):
            restored, m_el = step_b(restored, batch)
    assert abs(float(m_el["loss"]) - ref_loss) < 5e-4, \
        (float(m_el["loss"]), ref_loss)
