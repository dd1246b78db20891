"""Train states that fit: on one device the PPO trainer donates the
actor and critic states to their steps, and holds the frozen reference
and reward models as their compute-dtype cast, made once.  No array a
caller handed in is donated, so a caller that passes the same arrays as
the actor and the frozen reference (as the benchmark harness does) keeps
both intact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hybrid_engine import HybridEngine
from repro.core.ppo import PPOConfig, PPOTrainer
from repro.launch.mesh import make_mesh
from repro.models import reward as R
from repro.models import transformer as T
from repro.models.config import ModelConfig

BASE = dict(arch_type="dense", n_layers=2, d_model=64, n_heads=4,
            n_kv_heads=2, d_ff=128, vocab_size=97, remat=False)
PROMPTS = jnp.asarray(np.arange(4 * 6).reshape(4, 6) % 97, jnp.int32)


def _trainer(dtype, **kw):
    actor = ModelConfig(name="a", compute_dtype=dtype, **BASE)
    critic = ModelConfig(name="c", compute_dtype=dtype, **BASE)
    pa = T.init_params(actor, jax.random.PRNGKey(1))
    pc = R.init_params(critic, jax.random.PRNGKey(2))
    tr = PPOTrainer(actor_cfg=actor, critic_cfg=critic, actor_params=pa,
                    critic_params=pc, ref_params=pa, reward_params=pc,
                    ppo=PPOConfig(max_new_tokens=4),
                    engine=HybridEngine(actor, make_mesh(1, 1)), **kw)
    return tr, pa, pc


def _iterate(tr, i):
    rollout, _ = tr.generate_rollout(PROMPTS, jax.random.PRNGKey(i))
    exp, _ = tr.score_rollout(rollout)
    return tr.train_rlhf(exp)


def _host(tree):
    return jax.tree.map(np.asarray, tree)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_frozen_trees_survive_donated_steps(dtype):
    tr, pa, pc = _trainer(dtype)
    ref0, reward0 = _host(tr.ref_params), _host(tr.reward_params)
    handed = _host((pa, pc))
    old = tr.actor
    for i in range(2):
        m = _iterate(tr, i)
        assert all(np.isfinite(v) for v in m.values())
    # the steps donated the states they replaced ...
    assert all(x.is_deleted() for x in jax.tree.leaves(old))
    # ... and the frozen trees and the caller's arrays read as they did
    for a, b in zip(jax.tree.leaves(_host(tr.ref_params)),
                    jax.tree.leaves(ref0)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(_host(tr.reward_params)),
                    jax.tree.leaves(reward0)):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(_host((pa, pc))), jax.tree.leaves(handed)):
        np.testing.assert_array_equal(a, b)
    # the actor moved away from the reference it started as
    assert not np.array_equal(np.asarray(tr.actor.params["lm_head"]),
                              np.asarray(pa["lm_head"]))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_frozen_trees_in_the_compute_dtype(dtype):
    tr, pa, pc = _trainer(dtype)
    # kept as they are: the value head, applied uncast
    # (``reward.values``), and the embedding table, whose gathered rows
    # the forward casts
    for frozen, master in ((tr.ref_params, pa), (tr.reward_params, pc)):
        for k, v in frozen.items():
            want = (master[k].dtype if k in ("v_head", "embed")
                    else jnp.dtype(dtype))
            assert all(x.dtype == want for x in jax.tree.leaves(v)), k
    assert tr.ref_params["lm_head"].dtype == jnp.dtype(dtype)
    # what the forward reads of them is what it read of the masters
    seq = PROMPTS
    np.testing.assert_array_equal(
        np.asarray(R.values(tr.critic_cfg, tr.reward_params, seq)),
        np.asarray(R.values(tr.critic_cfg, pc, seq)))


def test_undonated_trainer_keeps_replaced_states():
    """``donate=False`` (the async pipeline, whose weight publisher holds
    earlier actor trees by reference): replaced states stay readable."""
    tr, _, _ = _trainer("bfloat16", donate=False)
    old = tr.actor
    _iterate(tr, 0)
    assert not any(x.is_deleted() for x in jax.tree.leaves(old))
    assert np.isfinite(np.asarray(old.params["lm_head"])).all()
