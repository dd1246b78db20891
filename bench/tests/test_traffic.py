"""Traffic is the same for the same seed, and every seed offers the same
work: the stage-3 prompt batches have one shape for every seed and
iteration; only their tokens and sampling keys differ."""
import jax
import numpy as np
import pytest

from bench.kinds import ppo_fixed as K
from bench.lib import spec as SP
from bench.lib import weights as W

CELL = SP.load_cell("ppo-opt1.3b-p256g256")
TR = CELL.traffic
V = CELL.config["models"]["actor"]["vocab_size"]
BIG = 2 ** 33 + 5


def batch(seed, i):
    p, k = K.rollout_inputs(TR, V, seed)(np.int32(i))
    return np.asarray(p), np.asarray(jax.random.key_data(k)
                                      if jax.dtypes.issubdtype(
                                          k.dtype, jax.dtypes.prng_key)
                                      else k)


def test_ppo_inputs_deterministic_per_seed():
    a, b = batch(BIG, 3), batch(BIG, 3)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


@pytest.mark.parametrize("other", [(BIG + 1, 3), (BIG, 4), (BIG + 2 ** 32, 3)])
def test_ppo_inputs_differ_by_seed_and_iteration(other):
    a, b = batch(BIG, 3), batch(*other)
    assert not np.array_equal(a[0], b[0])
    assert not np.array_equal(a[1], b[1])


def test_ppo_work_same_for_every_seed():
    shapes = {batch(s, i)[0].shape for s in (1, BIG) for i in (0, 7)}
    assert shapes == {(TR["batch"], TR["prompt_len"])}
    p = batch(BIG, 0)[0]
    assert p.dtype == np.int32 and p.min() >= 0 and p.max() < V


def test_weights_deterministic_and_seed_keeps_high_bits():
    import jax
    m = {"n_layers": 1, "d_model": 8, "n_heads": 2, "n_kv_heads": 1,
         "head_dim": 4, "d_ff": 16, "vocab_size": 32}
    init = SP.load_reference(CELL.config["reference"]).init
    a = W.make({"model": m}, {"model": "lm"}, 2 ** 40 + 1, init)
    b = W.make({"model": m}, {"model": "lm"}, 2 ** 40 + 1, init)
    c = W.make({"model": m}, {"model": "lm"}, 1, init)
    la, lb, lc = (jax.tree.leaves(x) for x in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(la[0], lc[0])
