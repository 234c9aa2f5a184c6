"""Loop-adjusted HLO analyzer: validated against hand-computed programs."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.launch import hlo_analysis as HA


def test_scan_trip_count_multiplies_flops():
    N, M = 9, 64

    def f(w, x):
        def body(h, _):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, None, length=N)
        return h

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((M, M), jnp.float32),
                         jax.ShapeDtypeStruct((8, M), jnp.float32)).compile()
    cost = HA.analyze(c.as_text())
    one = 2 * 8 * M * M
    assert N * one <= cost.flops <= N * one * 1.2, (cost.flops, N * one)
    assert any(t == N for _, t in cost.loops), cost.loops
    # raw cost_analysis counts the body once — the analyzer must exceed it
    raw = c.cost_analysis()["flops"]
    assert cost.flops > 3 * raw


def test_nested_scan_multiplier():
    def f(x):
        def outer(h, _):
            def inner(g, _):
                return g * 1.5 + 1.0, None
            g, _ = jax.lax.scan(inner, h, None, length=5)
            return g, None
        h, _ = jax.lax.scan(outer, x, None, length=3)
        return h

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((128,), jnp.float32)).compile()
    cost = HA.analyze(c.as_text())
    trips = dict(cost.loops)
    assert 3 in trips.values()
    assert 15 in trips.values(), trips          # 3 x 5 nested


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_collective_bytes_ring_model():
    mesh = compat.make_mesh((8,), ("model",))

    def f(x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(None, None))).sum()

    with jax.set_mesh(mesh):
        c = jax.jit(f, in_shardings=NamedSharding(mesh, P("model", None))) \
            .lower(jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile()
    cost = HA.analyze(c.as_text())
    # all-gather of a (8, 32) f32 local shard over 8 ranks: (g-1) * 1024 B
    ag = cost.per_collective.get("all-gather", 0)
    assert ag == pytest.approx(7 * 8 * 32 * 4, rel=0.01), cost.per_collective


def test_shape_bytes_parsing():
    assert HA._shape_bytes("f32[4,8]{1,0}") == 128
    assert HA._shape_bytes("bf16[10]") == 20
    assert HA._shape_bytes("(s32[], f32[2,2])") == 4 + 16
    assert HA._shape_bytes("pred[7]") == 7
    assert HA._shape_bytes("u8[3,3]") == 9
