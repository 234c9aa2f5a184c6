"""The control: the plain reference in bfloat16, in the program's place,
has to come out as not correct (the chip readings are in PERF.md)."""

import control


def test_control_is_not_correct(tiny):
    res = tiny("tiny-poisson", make_server=control.control_server)
    c = res["checks"]["mismatched_logits"]
    assert res["correct"] is False
    assert c["value"] > 0 and c["compared"] >= 10


def test_reference_agrees_with_the_program_oracle():
    import json

    import jax
    import jax.numpy as jnp

    import netgen
    import reference
    from conftest import DATA
    from repro import api

    cfg = json.loads((DATA / "tiny_cnn.json").read_text())
    w = netgen.make_weights(cfg, netgen.root_key(3))
    x = netgen.images(cfg, jax.random.PRNGKey(4), 64)
    want = api.oracle(netgen.quantized_net(cfg, w), x, mode="packed")
    assert bool(jnp.array_equal(reference.make_forward(cfg)(w, x), want))
    low = reference.make_forward(cfg, dtype=jnp.bfloat16)(w, x)
    assert int((low != want).sum()) > 0
