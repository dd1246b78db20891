"""Disaggregated async RLHF: equivalence, staleness guard, mesh split.

The async pipeline is only trustworthy if it is PROVABLY the same
training process as the synchronous one when configured to be:

- lockstep mode (queue depth 1, publish-every-step, max_lag=0) must be
  bit-identical to the sync pipeline — same reward-score trajectory,
  same per-iteration metrics minus wall-time telemetry, same actor and
  critic SHA-256 after N iterations;
- the one-step-stale leg must tag every rollout with its behavior
  policy version, report ``policy_lag`` deterministically, and emit
  importance-ratio guard metrics that move off 1.0 exactly on the
  stale iterations;
- the abort threshold must drop the run to on-policy lockstep.

The multi-mesh legs (marked ``multidevice``) run the same proofs on a
real rollout/train device split under the CI 8-fake-device flag.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (AsyncConfig, PPOConfig, PPOTrainer, RLHFEngine,
                        RLHFPipeline, StageConfig)
from repro.core import ppo as PPO
from repro.core.replay import RolloutBatch
from repro.data import ConstantTaskDataset, CopyTaskDataset, DataBlender
from repro.launch import mesh as M
from repro.models.config import ModelConfig

pytestmark = pytest.mark.async_rlhf

V = 64
ACTOR = ModelConfig(name="a", arch_type="dense", n_layers=1, d_model=32,
                    n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=V,
                    compute_dtype="float32", remat=False)
CRITIC = ACTOR.replace(name="c")
# wall-time / topology telemetry: legitimately differs between modes
WALL_KEYS = ("gen_tok_s", "reshard_s", "reshard_bytes", "publish_s",
             "publish_bytes", "queue_depth", "policy_lag",
             "is_ratio_mean", "is_ratio_max", "lockstep_fallback")
STAGES = StageConfig(sft_steps=2, sft_batch=4, rm_steps=2, rm_batch=4,
                     ppo_steps=4, ppo_batch=4, seed=0)


def tree_sha(tree) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


def run_pipeline(async_cfg, *, mesh=None, rollout_mesh=None,
                 ppo_kw=None, stages=STAGES):
    ds = [ConstantTaskDataset(200, 6, 6, V, seed=1),
          CopyTaskDataset(200, 6, 6, V, seed=2)]
    bl = DataBlender(ds, [0.7, 0.3], seed=0)
    eng = RLHFEngine(ACTOR, CRITIC, jax.random.PRNGKey(0), mesh=mesh,
                     rollout_mesh=rollout_mesh)
    pipe = RLHFPipeline(eng, bl, stages,
                        PPOConfig(max_new_tokens=4, temperature=1.0,
                                  **(ppo_kw or {})),
                        async_cfg=async_cfg)
    out = pipe.run()
    return out, pipe


def strip_wall(metrics: dict) -> dict:
    return {k: v for k, v in metrics.items() if k not in WALL_KEYS}


# ===================================================================== #
# lockstep: async must BE the sync pipeline, bit for bit
# ===================================================================== #
def test_lockstep_bit_identical_to_sync():
    out_s, p_s = run_pipeline(None)
    out_a, p_a = run_pipeline(AsyncConfig.lockstep())
    assert out_s["ppo_scores"] == out_a["ppo_scores"]
    assert len(p_s.log["stage3"]) == len(p_a.log["stage3"]) == \
        STAGES.ppo_steps
    for ms, ma in zip(p_s.log["stage3"], p_a.log["stage3"]):
        assert strip_wall(ms) == strip_wall(ma)
        # lockstep is on-policy by construction and says so
        assert ma["policy_lag"] == 0.0
        assert ma["is_ratio_mean"] == 1.0 and ma["is_ratio_max"] == 1.0
    assert tree_sha(p_s.trainer.actor) == tree_sha(p_a.trainer.actor)
    assert tree_sha(p_s.trainer.critic) == tree_sha(p_a.trainer.critic)
    assert tree_sha(p_s.trainer.ema) == tree_sha(p_a.trainer.ema)
    # the producer really ran free (on its own thread) and drained
    assert p_a.async_stats["produced"] == STAGES.ppo_steps
    assert p_a.async_stats["queue"]["dropped"] == 0
    assert p_a.async_stats["queue"]["max_depth"] <= 1


def test_lockstep_metrics_carry_async_telemetry():
    _, p_a = run_pipeline(AsyncConfig.lockstep())
    m = p_a.log["stage3"][0]
    for k in ("policy_lag", "is_ratio_mean", "is_ratio_max",
              "queue_depth", "reward_score", "gen_tok_s"):
        assert k in m


# ===================================================================== #
# one-step-stale leg: deterministic lag pattern + live ratio guard
# ===================================================================== #
def test_stale_leg_reports_policy_lag_and_guard():
    cfg = AsyncConfig(queue_depth=2, publish_every=2, max_lag=1)
    _, pipe = run_pipeline(cfg)
    lags = [m["policy_lag"] for m in pipe.log["stage3"]]
    # version gate + publish cadence 2 make the staleness pattern
    # deterministic: versions used are 0,0,2,2,... so lag alternates
    assert lags == [0.0, 1.0] * (STAGES.ppo_steps // 2)
    for m in pipe.log["stage3"]:
        if m["policy_lag"] == 0.0:
            assert m["is_ratio_mean"] == 1.0
            assert m["is_ratio_max"] == 1.0
        else:
            # behavior policy is one update behind: some token's ratio
            # must have moved off exactly 1.0
            assert m["is_ratio_max"] != 1.0
            assert m["is_ratio_mean"] > 0.0
    assert pipe.async_stats["queue"]["max_depth"] <= cfg.queue_depth


def test_abort_threshold_falls_back_to_lockstep():
    # any stale consume trips a threshold of 1.0 (ratio_max > 1 as soon
    # as the policy moves), so the run must drop to lockstep and stay
    cfg = AsyncConfig(queue_depth=2, publish_every=1, max_lag=1,
                      is_ratio_abort=1.0)
    _, pipe = run_pipeline(cfg)
    lags = [m["policy_lag"] for m in pipe.log["stage3"]]
    assert pipe.async_stats["lockstep_fallbacks"] >= 1
    trip = next(i for i, m in enumerate(pipe.log["stage3"])
                if m.get("lockstep_fallback"))
    # the fallback governs batches not yet admitted by the version
    # gate; at most max_lag already-in-flight stale batches may still
    # arrive, then the run is strictly on-policy
    assert all(lag == 0.0 for lag in lags[trip + 1 + cfg.max_lag:])


def test_async_config_validation():
    with pytest.raises(ValueError, match="queue_depth"):
        AsyncConfig(queue_depth=0)
    with pytest.raises(ValueError, match="max_lag"):
        AsyncConfig(max_lag=-1)
    # a publish cadence the version gate can never satisfy = deadlock
    with pytest.raises(ValueError, match="publish_every"):
        AsyncConfig(publish_every=3, max_lag=1)
    lk = AsyncConfig.lockstep()
    assert (lk.queue_depth, lk.publish_every, lk.max_lag) == (1, 1, 0)


# ===================================================================== #
# behavior logprobs are the SAMPLING-time logprobs (satellite fix)
# ===================================================================== #
def _tiny_trainer(**kw):
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    from repro.models import reward as R
    from repro.models import transformer as T
    return PPOTrainer(
        actor_cfg=ACTOR, critic_cfg=CRITIC,
        actor_params=T.init_params(ACTOR, k1),
        critic_params=R.init_params(CRITIC, k2),
        ref_params=T.init_params(ACTOR, k1),
        reward_params=R.init_params(CRITIC, k2),
        ppo=PPOConfig(max_new_tokens=4, temperature=1.0), **kw)


def test_behavior_logprobs_are_sampling_time_not_recomputed():
    # the behavior params are retained by reference, as the async
    # pipeline's weight publisher retains them: no donation
    tr = _tiny_trainer(donate=False)
    prompts = jnp.asarray(np.full((4, 6), 3, np.int32))
    rollout, _ = tr.generate_rollout(prompts, jax.random.PRNGKey(7))
    exp0, sm0 = tr.score_rollout(rollout, policy_lag=0)
    behavior = jax.tree.map(lambda x: x, tr.actor.params)
    tr.train_rlhf(exp0)                       # policy moves
    # scoring with the tagged behavior params reproduces the sampling-
    # time logprobs EXACTLY (same jitted graph, same weights) ...
    exp_b, _ = tr.score_rollout(rollout, behavior_params=behavior)
    assert np.array_equal(np.asarray(exp_b.logprobs),
                          np.asarray(exp0.logprobs))
    # ... while the pre-fix behavior (recompute from the updated actor)
    # yields different logprobs — it was silently hiding staleness
    exp_c, _ = tr.score_rollout(rollout)
    assert not np.array_equal(np.asarray(exp_c.logprobs),
                              np.asarray(exp_b.logprobs))
    # the guard sees the difference; on-policy it reports identity
    _, sm_stale = tr.score_rollout(rollout, behavior_params=behavior,
                                   policy_lag=1)
    assert sm_stale["is_ratio_max"] != 1.0
    assert sm0["is_ratio_mean"] == 1.0 and sm0["is_ratio_max"] == 1.0


def test_on_policy_first_step_ratio_is_one():
    # regression for the satellite: with exact behavior logprobs, the
    # FIRST PPO step of a fresh batch is exactly on-policy, so the
    # training ratio stays at 1 (up to the loss graph's own fusion)
    tr = _tiny_trainer()
    prompts = jnp.asarray(np.full((4, 6), 3, np.int32))
    exp, _ = tr.generate_experience(prompts, jax.random.PRNGKey(7))
    tm = tr.train_rlhf(exp)
    assert abs(tm["ratio_mean"] - 1.0) < 1e-5
    assert abs(tm["approx_kl"]) < 1e-6


def test_is_clip_clamps_importance_ratio():
    tr = _tiny_trainer()
    prompts = jnp.asarray(np.full((4, 6), 3, np.int32))
    exp, _ = tr.generate_experience(prompts, jax.random.PRNGKey(7))
    # fabricate a strongly off-policy batch: behavior logprobs shifted
    # down by 1 nat -> unclamped ratio would be e ~ 2.72 everywhere
    import dataclasses as dc
    off = exp._replace(logprobs=exp.logprobs - 1.0)
    ppo_clip = dc.replace(tr.ppo, is_clip=1.5)
    _, m_clip = PPO.actor_loss_fn(ACTOR, ppo_clip, tr.actor.params, off)
    _, m_raw = PPO.actor_loss_fn(ACTOR, tr.ppo, tr.actor.params, off)
    assert float(m_raw["ratio_mean"]) > 2.0
    assert float(m_clip["ratio_mean"]) <= 1.5 + 1e-6


# ===================================================================== #
# mesh split: parsing + oversubscription (single-device), real split
# (multidevice)
# ===================================================================== #
def test_disaggregated_mesh_spec_parsing():
    assert M._submesh_shape(6, "model", "--rollout-mesh") == (1, 6)
    assert M._submesh_shape(2, "data", "--train-mesh") == (2, 1)
    assert M._submesh_shape("4", "model", "--rollout-mesh") == (1, 4)
    assert M._submesh_shape("2,3", "model", "--rollout-mesh") == (2, 3)
    assert M._submesh_shape((2, 2), "data", "--train-mesh") == (2, 2)
    for bad in ("0", "1,2,3", "0,1", -1):
        with pytest.raises(ValueError):
            M._submesh_shape(bad, "model", "--rollout-mesh")


def test_disaggregated_meshes_oversubscription_raises():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="disaggregated"):
        M.make_disaggregated_meshes(rollout=n, train=1)


@pytest.mark.multidevice
def test_disaggregated_meshes_are_disjoint():
    rm, tm = M.make_disaggregated_meshes(rollout=2, train=2)
    assert dict(rm.shape) == {"data": 1, "model": 2}
    assert dict(tm.shape) == {"data": 2, "model": 1}
    r_devs = {d.id for d in rm.devices.flat}
    t_devs = {d.id for d in tm.devices.flat}
    assert not r_devs & t_devs
    rm2, tm2 = M.make_disaggregated_meshes(rollout="1,2", train="2,2")
    assert dict(tm2.shape) == {"data": 2, "model": 2}
    assert not ({d.id for d in rm2.devices.flat}
                & {d.id for d in tm2.devices.flat})


@pytest.mark.multidevice
def test_disaggregated_lockstep_matches_sync_split():
    """On a real rollout/train split, lockstep async == the sync
    pipeline run over the SAME split (generation on the rollout mesh,
    PPO on the training mesh) — bit for bit."""
    rm, tm = M.make_disaggregated_meshes(rollout=2, train=2)
    out_s, p_s = run_pipeline(None, mesh=tm, rollout_mesh=rm)
    out_a, p_a = run_pipeline(AsyncConfig.lockstep(), mesh=tm,
                              rollout_mesh=rm)
    assert out_s["ppo_scores"] == out_a["ppo_scores"]
    for ms, ma in zip(p_s.log["stage3"], p_a.log["stage3"]):
        assert strip_wall(ms) == strip_wall(ma)
    assert tree_sha(p_s.trainer.actor) == tree_sha(p_a.trainer.actor)
    assert tree_sha(p_s.trainer.critic) == tree_sha(p_a.trainer.critic)
    # weights really were published onto the rollout devices
    assert p_a.async_stats["publisher"]["total_publish_bytes"] > 0


@pytest.mark.multidevice
def test_disaggregated_stale_overlap_runs():
    """The overlap mode on a real device split: one-step-stale consume,
    deterministic lag pattern, bounded queue, guard metrics live."""
    rm, tm = M.make_disaggregated_meshes(rollout=2, train=2)
    cfg = AsyncConfig(queue_depth=2, publish_every=2, max_lag=1)
    _, pipe = run_pipeline(cfg, mesh=tm, rollout_mesh=rm)
    lags = [m["policy_lag"] for m in pipe.log["stage3"]]
    assert lags == [0.0, 1.0] * (STAGES.ppo_steps // 2)
    assert pipe.async_stats["queue"]["max_depth"] <= cfg.queue_depth
    assert any(m["is_ratio_max"] != 1.0 for m in pipe.log["stage3"])


@pytest.mark.multidevice
def test_cross_mesh_publish_lands_on_rollout_devices():
    from repro.sharding import strategy as S
    rm, tm = M.make_disaggregated_meshes(rollout=2, train=2)
    from repro.models import transformer as T
    params = T.init_params(ACTOR, jax.random.PRNGKey(0))
    sh = S.param_shardings(ACTOR, rm, "tp")
    out = S.cross_mesh_put(params, sh)
    leaf = jax.tree.leaves(out)[0]
    assert {d.id for d in leaf.devices()} <= {d.id for d in
                                              rm.devices.flat}
