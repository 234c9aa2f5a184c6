"""Trained fixture nets for tests that compare a quantized net's decisions
with its float reference.

Such a comparison means something only where the float net's decisions
do: an untrained LeNet-5 at width 0.25 maps every input to almost the
same logits, so which class wins is decided by tiny margins that any
change of random draws (or of quantization) flips for the whole batch.
These nets are trained briefly on the procedural ``SyntheticVision``
task and calibrated on a held-out batch of it, so their decisions depend
on the input and have margins a faithful conversion must preserve.
"""

import functools

import jax.numpy as jnp

from repro.data.synthetic import SyntheticVision
from repro.launch import serve_cnn
from repro.train.trainer import TrainConfig, train_ann

TRAIN = TrainConfig(steps=600, batch_size=64, lr=0.01)


@functools.lru_cache(maxsize=None)
def trained_lenet(pool_mode: str, *, calib_batch: int, seed: int = 0):
    """(static, params, item shape, calibration batch) of the LeNet-5
    smoke build after ``TRAIN`` SGD steps on ``SyntheticVision``."""
    static, params, item, _ = serve_cnn.build_float_net(
        "lenet5", smoke=True, pool_mode=pool_mode, seed=seed)
    params, _ = train_ann(static, params, SyntheticVision(item, seed=seed),
                          TRAIN, log=None)
    calib = SyntheticVision(item, seed=seed + 1).calibration_batch(
        calib_batch)
    return static, params, item, jnp.asarray(calib)
