"""Full-fledged 3-step RLHF pipeline (InstructGPT / DeepSpeed-Chat Fig. 1):

  Step 1  SFT          — supervised finetuning on prompt+chosen
  Step 2  RM           — pairwise reward-model finetuning
  Step 3  PPO (RLHF)   — Hybrid-Engine PPO with optional EMA + mixture

``RLHFEngine`` mirrors ``DeepSpeedRLHFEngine``: it owns the four models
(actor, ref, critic, reward) and the Hybrid Engine; ``RLHFPipeline.run``
is the single-script experience of §2.1.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import lora as LoRA
from repro.core.hybrid_engine import HybridEngine
from repro.core.ppo import PPOConfig, PPOTrainer
from repro.core.replay import (AsyncConfig, ExperienceProducer,
                               ReplayQueue, WeightPublisher)
from repro.data.blending import DataBlender
from repro.models import reward as R
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.training import schedules
from repro.training.steps import lm_train_step, reward_train_step
from repro.training.train_state import TrainState


@dataclasses.dataclass
class StageConfig:
    sft_steps: int = 50
    sft_batch: int = 8
    sft_lr: float = 3e-4
    rm_steps: int = 50
    rm_batch: int = 8
    rm_lr: float = 3e-4
    ppo_steps: int = 30
    ppo_batch: int = 8
    seed: int = 0


class RLHFEngine:
    """Owns actor/ref/critic/reward params + the Hybrid Engine."""

    def __init__(self, actor_cfg: ModelConfig, critic_cfg: ModelConfig,
                 key, mesh=None, train_strategy="zero3",
                 rollout_mesh=None):
        self.actor_cfg, self.critic_cfg = actor_cfg, critic_cfg
        k1, k2 = jax.random.split(key)
        self.actor_params = T.init_params(actor_cfg, k1)
        self.critic_params = R.init_params(critic_cfg, k2)
        self.ref_params = None       # snapshotted from SFT actor
        self.reward_params = None    # snapshotted from trained RM
        self.hybrid = (HybridEngine(actor_cfg, mesh,
                                    train_strategy=train_strategy)
                       if mesh is not None else None)
        # disaggregated mode: a dedicated generation mesh, disjoint from
        # the training mesh (launch.mesh.make_disaggregated_meshes)
        self.rollout_mesh = rollout_mesh


class RLHFPipeline:
    """3-stage driver with optional fault tolerance.

    Pass ``checkpointer`` (a
    :class:`repro.training.checkpoint.CheckpointManager`) to make the
    run durable: stage boundaries commit the stage-1/2 outputs, and
    every ``save_every`` PPO iterations the FULL stage-3 state — actor
    and critic TrainStates including Adam moments, the EMA shadow, the
    frozen ref/reward params, the PRNG carry, the data-blender cursor,
    step counters, and the metrics log — is snapshotted device-to-host
    and written in the background.  ``run`` / ``run_ppo`` then resume
    from the latest valid checkpoint, continuing bit-identically to an
    uninterrupted run (tests/test_checkpoint_resume.py is the proof).
    """

    def __init__(self, engine: RLHFEngine, blender: DataBlender,
                 stages: StageConfig, ppo: PPOConfig,
                 checkpointer=None, save_every: int = 1,
                 async_cfg: Optional[AsyncConfig] = None):
        self.e = engine
        self.blender = blender
        self.stages = stages
        self.ppo = ppo
        self.ckpt = checkpointer
        self.save_every = save_every
        self.async_cfg = async_cfg  # disaggregated/overlapped stage 3
        self.iter_hook = None      # called as iter_hook(i) at the top of
        #                            each PPO iteration (telemetry; the
        #                            crash-injection tests die here)
        self.rollout_hook = None   # async mode: called as rollout_hook(i)
        #                            on the PRODUCER thread before batch i
        #                            (soak tests inject slow phases here)
        self.async_stats = {}      # queue/publisher/producer telemetry
        self.log = {"stage1": [], "stage2": [], "stage3": []}
        self.rm_acc = []
        self.timings = {}          # seconds per stage
        self.gen_tok_s = 0.0       # mean stage-3 generation throughput

    # ------------------------- Step 1: SFT ------------------------- #
    def run_sft(self):
        cfg, st = self.e.actor_cfg, self.stages
        state = TrainState.create(self.e.actor_params)
        lr = schedules.cosine_warmup(st.sft_lr, st.sft_steps // 10 + 1,
                                     st.sft_steps)
        step_fn = jax.jit(partial(lm_train_step, cfg))
        with obs.span("pipeline.stage1") as sp:
            for i, batch in enumerate(self.blender.sft_batches(
                    st.sft_batch, st.sft_steps)):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                state, m = step_fn(state, batch, lr(i))
                self.log["stage1"].append(float(m["loss"]))
        self.timings["stage1"] = sp.seconds
        self.e.actor_params = state.params
        self.e.ref_params = jax.tree.map(lambda x: x, state.params)
        if self.ckpt is not None:
            self.ckpt.save(self.SFT_STEP,
                           {"actor": self.e.actor_params,
                            "ref": self.e.ref_params},
                           self._meta("sft_done"))
        return self.log["stage1"]

    # ----------------------- Step 2: Reward ------------------------ #
    def run_reward(self):
        cfg, st = self.e.critic_cfg, self.stages
        state = TrainState.create(self.e.critic_params)
        lr = schedules.cosine_warmup(st.rm_lr, st.rm_steps // 10 + 1,
                                     st.rm_steps)
        step_fn = jax.jit(partial(reward_train_step, cfg))
        accs = []
        with obs.span("pipeline.stage2") as sp:
            for i, batch in enumerate(self.blender.reward_batches(
                    st.rm_batch, st.rm_steps)):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                state, m = step_fn(state, batch, lr(i))
                self.log["stage2"].append(float(m["rm_loss"]))
                accs.append(float(m["rm_acc"]))
        self.timings["stage2"] = sp.seconds
        self.e.reward_params = state.params
        self.e.critic_params = jax.tree.map(lambda x: x, state.params)
        self.rm_acc = accs
        if self.ckpt is not None:
            self.ckpt.save(self.RM_STEP,
                           {"actor": self.e.actor_params,
                            "ref": self.e.ref_params,
                            "critic": self.e.critic_params,
                            "reward": self.e.reward_params},
                           self._meta("rm_done"))
        return accs

    # ------------------------ Step 3: PPO -------------------------- #
    def run_ppo(self, key=None):
        st = self.stages
        key = key if key is not None else jax.random.PRNGKey(st.seed + 3)
        start, restored = 0, None
        if self.ckpt is not None:
            latest = self.ckpt.latest_step()
            if (latest is not None and self.ckpt.restore_metadata(
                    latest).get("stage") == "ppo"):
                restored = self._restore_ppo(latest, key)
                key = restored["rng"]
                start = restored["ppo_iter"]
        trainer = PPOTrainer(
            actor_cfg=self.e.actor_cfg, critic_cfg=self.e.critic_cfg,
            actor_params=self.e.actor_params,
            critic_params=self.e.critic_params,
            ref_params=self.e.ref_params,
            reward_params=self.e.reward_params,
            ppo=self.ppo, engine=self.e.hybrid,
            rollout_mesh=getattr(self.e, "rollout_mesh", None),
            # the async weight publisher retains earlier actor trees by
            # reference, which a donated step would free
            donate=self.async_cfg is None)
        if restored is not None:
            trainer.load_state_tree(restored["trainer"])
        ptx_iter = (self.blender.pretrain_batches(st.ppo_batch,
                                                  st.ppo_steps, skip=start)
                    if self.ppo.ptx_coef > 0 else None)
        scores = [m["reward_score"] for m in self.log["stage3"]]
        elapsed = self.timings.get("stage3", 0.0) if restored else 0.0
        with obs.span("pipeline.stage3") as sp:
            if self.async_cfg is not None:
                self._run_ppo_async(trainer, key, start, scores, ptx_iter,
                                    sp, elapsed)
            else:
                for i, batch in enumerate(self.blender.prompt_batches(
                        st.ppo_batch, st.ppo_steps, skip=start), start=start):
                    if self.iter_hook is not None:
                        self.iter_hook(i)
                    obs.step(i)
                    key, k = jax.random.split(key)
                    exp, gm = trainer.generate_experience(
                        jnp.asarray(batch["prompts"]), k)
                    ptx = None
                    if ptx_iter is not None:
                        ptx = {k2: jnp.asarray(v)
                               for k2, v in next(ptx_iter).items()}
                    tm = trainer.train_rlhf(exp, ptx)
                    scores.append(gm["reward_score"])
                    self.log["stage3"].append({**gm, **tm})
                    if (self.ckpt is not None and self.save_every
                            and ((i + 1) % self.save_every == 0
                                 or i == st.ppo_steps - 1)):
                        self.timings["stage3"] = elapsed + sp.seconds
                        self._save_ppo(trainer, key, i + 1)
        self.timings["stage3"] = elapsed + sp.seconds
        # serving-grade generation telemetry (engine early-exit decode);
        # kept out of ``timings`` which holds seconds only
        if self.log["stage3"]:
            self.gen_tok_s = float(np.mean(
                [m["gen_tok_s"] for m in self.log["stage3"]]))
        self.e.actor_params = trainer.actor.params
        self.trainer = trainer
        if self.ckpt is not None:
            self.ckpt.wait_for_save()     # durable before we return
        return scores

    # ------------------- Step 3, async (disaggregated) ------------- #
    def _run_ppo_async(self, trainer, key, start, scores, ptx_iter,
                       stage_span, elapsed):
        """Overlapped stage 3: a free-running producer thread generates
        batch N+1 on the rollout mesh while this (consumer) thread
        scores + trains batch N on the training mesh.

        Staleness protocol: the consumer's policy ``version`` counts
        completed PPO steps; after every ``publish_every``-th step the
        fresh actor params are pushed to the rollout layout and the
        train-layout tree is retained per version.  The producer may
        generate batch ``i`` only under a published version
        ``>= i - max_lag``, each rollout is scored with its OWN tagged
        behavior policy (exact importance ratios), and consuming with
        ``lag > 0`` emits the guard metrics; ``is_ratio_max`` above
        ``is_ratio_abort`` drops the run to on-policy lockstep.

        With ``max_lag=0`` (lockstep) the gate admits exactly the data,
        params, and PRNG chain of the sync loop, so the run is
        bit-identical to it — including checkpoints, because this
        thread mirrors the sync per-iteration key split (the producer
        owns the live chain) and saves the same carry.
        """
        st, acfg = self.stages, self.async_cfg
        publisher = WeightPublisher(shardings=trainer.publish_shardings(),
                                    keep=acfg.max_lag + 2,
                                    async_push=acfg.async_publish)
        publisher.publish(trainer.actor.params, start)
        queue = ReplayQueue(acfg.queue_depth)
        producer = ExperienceProducer(
            trainer=trainer, key=key, start=start, steps=st.ppo_steps,
            batches=self.blender.prompt_batches(st.ppo_batch,
                                                st.ppo_steps, skip=start),
            queue=queue, publisher=publisher, cfg=acfg,
            rollout_hook=self.rollout_hook)
        producer.start()
        version, fallbacks = start, 0
        try:
            for i in range(start, st.ppo_steps):
                if self.iter_hook is not None:
                    self.iter_hook(i)
                obs.step(i)
                # mirror the sync PRNG carry (the producer holds the
                # live generation chain) so checkpoints stay identical
                key, _ = jax.random.split(key)
                item = queue.get(timeout=acfg.get_timeout_s)
                lag = version - item.rollout.version
                exp, sm = trainer.score_rollout(
                    item.rollout,
                    behavior_params=publisher.train_params(
                        item.rollout.version),
                    policy_lag=lag)
                gm = {**item.gen_metrics, **sm,
                      "queue_depth": float(len(queue))}
                ps = publisher.last_publish_stats
                if ps:
                    gm["publish_s"] = float(ps["seconds"])
                    gm["publish_bytes"] = float(ps["bytes"])
                ptx = None
                if ptx_iter is not None:
                    ptx = {k2: jnp.asarray(v)
                           for k2, v in next(ptx_iter).items()}
                tm = trainer.train_rlhf(exp, ptx)
                version += 1
                tripped = (acfg.is_ratio_abort is not None and lag > 0
                           and sm["is_ratio_max"] > acfg.is_ratio_abort)
                if tripped:
                    # staleness guard: fall back to on-policy lockstep
                    # for the rest of the run.  Flip the producer's gate
                    # BEFORE publishing this version — otherwise the
                    # producer could admit one more stale batch between
                    # the publish and the flip.
                    producer.force_lockstep()
                    fallbacks += 1
                    gm["lockstep_fallback"] = 1.0
                if (tripped or version % acfg.publish_every == 0
                        or producer.lockstep_active):
                    publisher.publish(trainer.actor.params, version)
                scores.append(gm["reward_score"])
                self.log["stage3"].append({**gm, **tm})
                if (self.ckpt is not None and self.save_every
                        and ((i + 1) % self.save_every == 0
                             or i == st.ppo_steps - 1)):
                    self.timings["stage3"] = elapsed + stage_span.seconds
                    self._save_ppo(trainer, key, i + 1)
        finally:
            producer.stop()
            publisher.close()      # wakes a version-gated producer
            queue.cancel()         # wakes a blocked put
            producer.join(timeout=60.0)
            self.async_stats = {
                "queue": queue.stats(), "publisher": publisher.stats(),
                "produced": producer.produced,
                "lockstep_fallbacks": fallbacks,
            }
        if producer.error is not None:
            raise RuntimeError("rollout producer failed") \
                from producer.error

    # -------------------- checkpoint/resume seam ------------------- #
    # monotonic checkpoint step ids: stage boundaries, then one per
    # completed PPO iteration (k completed -> RM_STEP + k)
    SFT_STEP, RM_STEP = 1, 2

    def _meta(self, stage: str) -> dict:
        return {"stage": stage, "log": self.log, "rm_acc": self.rm_acc,
                "timings": self.timings}

    def _save_ppo(self, trainer, key, done: int) -> None:
        """Commit the FULL stage-3 state after ``done`` completed
        iterations: trainer states (moments + EMA), frozen ref/reward
        params, the PRNG carry that iteration ``done`` will split, and
        (in metadata) the data cursor + metrics log."""
        tree = {"trainer": trainer.state_tree(),
                "ref": trainer.ref_params,
                "reward": trainer.reward_params,
                "rng": np.asarray(key)}
        self.ckpt.save(self.RM_STEP + done, tree,
                       dict(self._meta("ppo"), ppo_iter=done))

    def _restore_ppo(self, step: int, key) -> dict:
        """Rebuild stage-3 state from checkpoint ``step``.  The restore
        target (`like`) is pure structure — ``jax.eval_shape`` trees, no
        allocation; sharding commitment happens later in
        :meth:`PPOTrainer.load_state_tree` against the *current* mesh,
        which is what makes cross-topology resume work."""
        from repro.core import ema as EMA
        like = {
            "trainer": {
                "actor": jax.eval_shape(TrainState.create,
                                        self.e.actor_params),
                "critic": jax.eval_shape(TrainState.create,
                                         self.e.critic_params),
                "ema": (jax.eval_shape(EMA.init, self.e.actor_params)
                        if self.ppo.use_ema else None),
            },
            # the trainer holds the frozen models as their compute-dtype
            # cast, and saves them so
            "ref": jax.eval_shape(partial(R.frozen, self.e.actor_cfg),
                                  self.e.actor_params),
            "reward": jax.eval_shape(partial(R.frozen, self.e.critic_cfg),
                                     self.e.critic_params),
            "rng": np.asarray(key),
        }
        tree, meta = self.ckpt.restore(like, step=step)
        self.e.ref_params = tree["ref"]
        self.e.reward_params = tree["reward"]
        self.e.actor_params = tree["trainer"]["actor"].params
        self.e.critic_params = tree["trainer"]["critic"].params
        self.log = meta["log"]
        self.rm_acc = meta["rm_acc"]
        self.timings = meta["timings"]
        return {"trainer": tree["trainer"],
                "rng": jnp.asarray(tree["rng"]),
                "ppo_iter": int(meta["ppo_iter"])}

    def _restore_boundary(self, step: int, meta: dict) -> None:
        """Adopt a stage-boundary checkpoint (skip re-running the
        completed stages)."""
        like = {"actor": jax.eval_shape(lambda t: t, self.e.actor_params),
                "ref": jax.eval_shape(lambda t: t, self.e.actor_params)}
        if meta["stage"] == "rm_done":
            like["critic"] = jax.eval_shape(lambda t: t,
                                            self.e.critic_params)
            like["reward"] = jax.eval_shape(lambda t: t,
                                            self.e.critic_params)
        tree, meta = self.ckpt.restore(like, step=step)
        self.e.actor_params = tree["actor"]
        self.e.ref_params = tree["ref"]
        if "critic" in tree:
            self.e.critic_params = tree["critic"]
            self.e.reward_params = tree["reward"]
        self.log = meta["log"]
        self.rm_acc = meta["rm_acc"]
        self.timings = meta["timings"]

    def maybe_restore(self):
        """(stage, step) of the latest valid checkpoint, or None."""
        if self.ckpt is None:
            return None
        step = self.ckpt.latest_step()
        if step is None:
            return None
        return self.ckpt.restore_metadata(step).get("stage"), step

    # --------------------------- driver ---------------------------- #
    def run(self, key=None):
        """End-to-end 3-stage run; with a checkpointer, an elastic one:
        a rerun after a crash fast-forwards past completed stages and
        resumes stage 3 mid-stream from the latest valid checkpoint."""
        resume = self.maybe_restore()
        stage = resume[0] if resume else None
        if stage == "ppo":
            pass                  # run_ppo restores everything itself
        elif stage in ("sft_done", "rm_done"):
            self._restore_boundary(resume[1],
                                   {"stage": stage})
        if stage is None:
            self.run_sft()
        if stage in (None, "sft_done"):
            self.run_reward()
        scores = self.run_ppo(key)
        return {"sft_loss": self.log["stage1"], "rm_acc": self.rm_acc,
                "ppo_scores": scores, "timings": self.timings}
