"""With the timed path broken underneath, a run's ``correct`` is false.

Faults a served CNN cell can have: an answer altered where it is
produced (one logit of a plan call), half of the batch left out (the
plan's second half of rows answered with the first half's), and a ticket
handed the wrong rows by the queue.  A training step or an exchange
between chips is not on these cells' path."""

import jax.numpy as jnp
import pytest

from repro.core import engine
from repro.launch import serve_cnn


def _alter_one_logit(out):
    return out.at[0, 0].add(1.0)


def _half_batch(out):
    n = out.shape[0]
    if n < 2:
        return out + 1.0
    half = n // 2
    return jnp.concatenate([out[:half], out[:n - half]])


@pytest.mark.parametrize("fault", [_alter_one_logit, _half_batch],
                         ids=["answer_altered", "half_batch_left_out"])
def test_broken_plan_is_not_correct(tiny, monkeypatch, fault):
    call = engine.CompiledPlan.__call__
    monkeypatch.setattr(engine.CompiledPlan, "__call__",
                        lambda self, x: fault(call(self, x)))
    res = tiny("tiny-closed")
    assert res["correct"] is False
    assert res["checks"]["mismatched_logits"]["value"] > 0


def test_rows_handed_to_the_wrong_ticket_is_not_correct(tiny, monkeypatch):
    resolve = serve_cnn.MicroBatchQueue._resolve

    def shifted(self, group, logits, t0):
        resolve(self, group, jnp.roll(logits, 1, axis=0), t0)
    monkeypatch.setattr(serve_cnn.MicroBatchQueue, "_resolve", shifted)
    res = tiny("tiny-poisson")
    assert res["correct"] is False


def test_sound_run_is_correct(tiny):
    assert tiny("tiny-closed")["correct"] is True
