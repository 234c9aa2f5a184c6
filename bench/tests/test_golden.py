"""The seeded weights, inputs and reference logits, pinned to what the
harness made before the model families moved out of it (on the CPU,
``JAX_PLATFORMS=cpu``): every seed draws the same keys and the family
computes the same arrays, bit for bit.  The trace reduction's kernel
join is pinned in ``test_spantrace``, the logical work in ``test_work``.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import netgen
import readers
import run
from conftest import DATA


def digest(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(tree):
        a = np.asarray(leaf)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


# (config, seed): digests of the weights, 16 input images, the
# reference's logits on them and the bfloat16 control's
GOLDEN = {
    ("tiny_cnn", 3): ("5b5c88acd52d1b94", "cc78fb409aff41a4",
                      "0743482690eba685", "68f7f61d13d6df9c"),
    ("tiny_cnn", 2**31 + 11): ("2bbd44ebd7e2e8c2", "0f7ec74709331b8c",
                               "3fb6cdcf6573cd61", "922e6aeefe4030c6"),
    ("lenet5_r4_bitserial", 3): ("29826bdf40434ddd", "3ae2dd259d844085",
                                 "50b0d9e98c293ea9", "4f556bb16d9ba7ad"),
    ("lenet5_r4_bitserial", 2**31 + 11): (
        "b9de747d90486f46", "5a14df51bb2dc4e2", "2b45d27279773c11",
        "a44059718fe6ea85"),
}


@pytest.mark.parametrize("name,seed", list(GOLDEN),
                         ids=[f"{n}-{s}" for n, s in GOLDEN])
def test_weights_images_and_logits_are_the_parents(name, seed):
    path = DATA / f"{name}.json"
    cfg = run.config_file(path if path.is_file()
                          else run.BENCH / "configs" / f"{name}.json")
    family = readers.family(cfg)
    key = netgen.root_key(seed)
    w = family.make_weights(cfg, jax.random.fold_in(key, 0))
    x = netgen.images(cfg, jax.random.fold_in(key, 1), 16)
    got = (digest(w), digest(x), digest(family.make_forward(cfg)(w, x)),
           digest(family.make_forward(cfg, jnp.bfloat16)(w, x)))
    assert got == GOLDEN[name, seed]
