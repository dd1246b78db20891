"""PPO trainer — the ``DeepSpeedPPOTrainer`` analogue.

    trainer = PPOTrainer(engine=rlhf_engine, ppo=PPOConfig(...))
    for batch in prompt_loader:
        exp = trainer.generate_experience(batch, key)   # inference phase
        metrics = trainer.train_rlhf(exp)               # training phase

``generate_experience`` runs under the Hybrid Engine's TP layout;
``train_rlhf`` under ZeRO-3.  Losses follow DeepSpeed-Chat / InstructGPT:
clipped surrogate for the actor (+ optional pretrain-mixture term),
clipped value loss for the critic, EMA collection of actor weights.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import ema as EMA
from repro.core import experience as X
from repro.core.hybrid_engine import HybridEngine
from repro.models import reward as R
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.serving.engine import GenerationEngine, count_moe
from repro.training.steps import lm_loss_fn
from repro.training.train_state import TrainState


@dataclasses.dataclass(frozen=True)
class PPOConfig:
    max_new_tokens: int = 32
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None   # enables early-exit decode when set
    decode_chunk: int = 32         # decode dispatch granularity (engine)
    # best-of-n experience generation: each prompt is sampled n times
    # (fixed-shape prompt batches are row-tiled; request lists are
    # expanded with per-copy seeds); with the paged engine's prefix
    # cache on, the request path reuses each prompt's prefilled KV
    # blocks, so the prompt prefill cost is paid once, not n times
    n_samples_per_prompt: int = 1
    kv_layout: str = "dense"       # generation engine KV layout
    kv_block_size: int = 16        # paged: tokens per KV block
    prefix_cache: bool = False     # paged: prefix-aware block reuse
    # int8 KV cache for experience generation (both layouts): ~3.5x more
    # cached tokens per KV byte at a bounded logit-error budget — only
    # the generation engine's cfg flips, training forwards are untouched
    kv_quant: bool = False
    kl_coef: float = 0.1
    clip_eps: float = 0.2
    value_clip: float = 0.2
    clip_reward: float = 5.0
    gamma: float = 1.0
    lam: float = 0.95
    lr_actor: float = 1e-5
    lr_critic: float = 5e-6
    ptx_coef: float = 0.0          # mixture training weight (0 = off)
    ema_decay: float = 0.992
    use_ema: bool = True
    # async (off-policy) staleness guard: clamp the per-token importance
    # ratio against the tagged behavior policy into [1/is_clip, is_clip].
    # None (the default) traces the identical on-policy loss graph, so
    # sync runs are bitwise unaffected.
    is_clip: Optional[float] = None


# ===================================================================== #
# Pure loss / step functions (jitted once per shape)
# ===================================================================== #
# the step metrics' key for a MoE model's counts (held slots, held
# experts given a slot), taken out of the metrics into the tracer's
# counters when the host reads the step's values
MOE_COUNTS = "moe_counts"


def actor_logprobs(cfg: ModelConfig, params, sequences, moe_counts=False):
    """log p of each next token; with ``moe_counts`` also the forward's
    MoE counts (:func:`repro.models.transformer.forward`)."""
    hidden, _, _, counts = T.forward(cfg, params, tokens=sequences,
                                     mode="full", moe_counts=True)
    lp = T.per_token_logprobs(cfg, params, hidden[:, :-1], sequences[:, 1:])
    return (lp, counts) if moe_counts else lp


def actor_loss_fn(cfg: ModelConfig, ppo: PPOConfig, params, exp: X.Experience,
                  ptx_batch=None):
    logp, counts = actor_logprobs(cfg, params, exp.sequences, moe_counts=True)
    ratio = jnp.exp(logp - exp.logprobs)
    if ppo.is_clip is not None:
        ratio = jnp.clip(ratio, 1.0 / ppo.is_clip, ppo.is_clip)
    a = exp.advantages
    l1 = -a * ratio
    l2 = -a * jnp.clip(ratio, 1 - ppo.clip_eps, 1 + ppo.clip_eps)
    n = jnp.maximum(exp.mask.sum(), 1.0)
    pg_loss = (jnp.maximum(l1, l2) * exp.mask).sum() / n
    loss = pg_loss
    metrics = {"pg_loss": pg_loss,
               "ratio_mean": (ratio * exp.mask).sum() / n,
               "approx_kl": ((exp.logprobs - logp) * exp.mask).sum() / n}
    if T.moe_layers(cfg):
        metrics[MOE_COUNTS] = counts
    if ptx_batch is not None and ppo.ptx_coef > 0:
        ptx, _ = lm_loss_fn(cfg, params, ptx_batch)
        loss = loss + ppo.ptx_coef * ptx
        metrics["ptx_loss"] = ptx
    return loss, metrics


def critic_loss_fn(cfg: ModelConfig, ppo: PPOConfig, params,
                   exp: X.Experience):
    v, counts = R.values(cfg, params, exp.sequences, moe_counts=True)
    v = v[:, :-1]
    v_clip = exp.values + jnp.clip(v - exp.values, -ppo.value_clip,
                                   ppo.value_clip)
    n = jnp.maximum(exp.mask.sum(), 1.0)
    l = jnp.maximum((v - exp.returns) ** 2, (v_clip - exp.returns) ** 2)
    loss = 0.5 * (l * exp.mask).sum() / n
    metrics = {"v_loss": loss, "v_mean": (v * exp.mask).sum() / n}
    if T.moe_layers(cfg):
        metrics[MOE_COUNTS] = counts
    return loss, metrics


def actor_step(cfg: ModelConfig, ppo: PPOConfig, state: TrainState,
               exp: X.Experience, ptx_batch=None):
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: actor_loss_fn(cfg, ppo, p, exp, ptx_batch),
        has_aux=True)(state.params)
    state, gnorm = state.apply_gradients(grads, lr=ppo.lr_actor)
    return state, dict(metrics, actor_loss=loss, actor_gnorm=gnorm)


def critic_step(cfg: ModelConfig, ppo: PPOConfig, state: TrainState,
                exp: X.Experience):
    (loss, metrics), grads = jax.value_and_grad(
        lambda p: critic_loss_fn(cfg, ppo, p, exp),
        has_aux=True)(state.params)
    state, gnorm = state.apply_gradients(grads, lr=ppo.lr_critic)
    return state, dict(metrics, critic_gnorm=gnorm)


def make_experience(actor_cfg: ModelConfig, critic_cfg: ModelConfig,
                    ppo: PPOConfig, actor_params, ref_params, critic_params,
                    reward_params, sequences, response_mask,
                    attn_mask=None):
    """Score a generated batch: logprobs, ref logprobs, values, rewards,
    GAE.  Pure function — jitted by the trainer; also the dry-run's
    'experience scoring' graph.  ``attn_mask`` marks the real (prompt +
    generated) tokens of each row so the reward model scores the last
    real token; ``None`` means the batch has no padding tail (the
    fixed-shape path).  Returns (experience, reward scores, the MoE
    counts of each role whose model has MoE layers)."""
    if attn_mask is None:
        attn_mask = jnp.ones(sequences.shape, jnp.float32)
    moe = {}

    def run(role, cfg, fn, *args):
        out, counts = fn(cfg, *args, moe_counts=True)
        if T.moe_layers(cfg):
            moe[role] = counts
        return out

    logp = run("actor", actor_cfg, actor_logprobs, actor_params, sequences)
    ref_logp = run("ref", actor_cfg, actor_logprobs, ref_params, sequences)
    values = run("critic", critic_cfg, R.values, critic_params,
                 sequences)[:, :-1]
    score = run("reward", critic_cfg, R.end_scores, reward_params,
                sequences, attn_mask)
    mask = response_mask[:, 1:].astype(jnp.float32)
    rewards = X.kl_rewards(logp, ref_logp, mask, score,
                           kl_coef=ppo.kl_coef,
                           clip_reward=ppo.clip_reward)
    adv, ret = X.gae(rewards, values, mask, gamma=ppo.gamma, lam=ppo.lam)
    return X.Experience(sequences=sequences, logprobs=logp,
                        ref_logprobs=ref_logp, values=values,
                        rewards=rewards, advantages=adv, returns=ret,
                        mask=mask), score, moe


def staleness_guard_stats(cfg: ModelConfig, params, sequences,
                          behavior_logp, mask):
    """Async-mode staleness telemetry: per-token importance ratio of the
    CURRENT training policy against the tagged behavior policy (the one
    that sampled the rollout).  Pure; jitted by the trainer and only
    dispatched when ``policy_lag > 0`` — lockstep/sync graphs are
    untouched."""
    logp = actor_logprobs(cfg, params, sequences)
    ratio = jnp.exp(logp - behavior_logp)
    n = jnp.maximum(mask.sum(), 1.0)
    return {"is_ratio_mean": (ratio * mask).sum() / n,
            "is_ratio_max": jnp.max(jnp.where(mask > 0, ratio, 1.0))}


# ===================================================================== #
# Trainer
# ===================================================================== #
def _own(tree):
    """Copies of the arrays of ``tree``, which the trainer may donate."""
    return jax.tree.map(lambda x: jnp.array(x, copy=True), tree)


class PPOTrainer:
    """Stage-3 trainer.  The frozen reference and reward models are held
    as their compute-dtype cast (:func:`repro.models.reward.frozen`:
    all but the embedding table), made once here: what every forward
    reads of them, in about half the memory of fp32 masters.  On one
    device the actor and critic steps
    donate their train state, so the old and the new state are never
    held at once; the states start as copies of the arrays handed in,
    so no caller's array is donated.  ``donate=False`` keeps every
    state the steps replace alive, for holders of earlier states (the
    async pipeline's weight publisher retains them by reference)."""

    def __init__(self, *, actor_cfg: ModelConfig, critic_cfg: ModelConfig,
                 actor_params, critic_params, ref_params, reward_params,
                 ppo: PPOConfig, engine: Optional[HybridEngine] = None,
                 rollout_mesh=None, donate: bool = True):
        self.actor_cfg, self.critic_cfg, self.ppo = actor_cfg, critic_cfg, ppo
        self.engine = engine
        self.mesh = engine.mesh if engine is not None else None
        self._multi = (self.mesh is not None and int(np.prod(
            list(self.mesh.shape.values()))) > 1)
        self._donate = donate and not self._multi
        if self._donate:
            actor_params, critic_params = _own((actor_params, critic_params))
        self.actor = TrainState.create(actor_params)
        self.critic = TrainState.create(critic_params)
        self.ref_params = R.frozen(actor_cfg, ref_params)
        self.reward_params = R.frozen(critic_cfg, reward_params)

        if self._multi:
            from repro.sharding import strategy as S
            # training layout: `train_strategy` params, ZeRO-`zero` fp32
            # Adam moments (sharded over the data axes); frozen scoring
            # models live in the TP layout (they are only ever read).
            # The critic/reward trees carry the value-head structure, so
            # their shardings resolve from reward.param_specs.
            crit_specs = R.param_specs(critic_cfg)
            self.actor = engine.shard_train_state(self.actor, actor_cfg)
            self.critic = engine.shard_train_state(self.critic, critic_cfg,
                                                   specs=crit_specs)
            self.ref_params = jax.device_put(
                self.ref_params, S.param_shardings(actor_cfg, self.mesh,
                                                   "tp"))
            self.reward_params = jax.device_put(
                self.reward_params, S.shardings_for_tree(crit_specs,
                                                         self.mesh, "tp"))
            # activation constraints inside the loss forwards: batch over
            # `data` (keeps GSPMD from replicating activations)
            actor_cfg = actor_cfg.replace(batch_axes=("data",),
                                          tp_axis="model")
            critic_cfg = critic_cfg.replace(batch_axes=("data",),
                                            tp_axis="model")
        self._step_actor_cfg, self._step_critic_cfg = actor_cfg, critic_cfg
        self.ema = EMA.init(self.actor.params) if ppo.use_ema else None

        gen_opts = dict(max_new_tokens=ppo.max_new_tokens,
                        temperature=ppo.temperature, top_k=ppo.top_k,
                        top_p=ppo.top_p, eos_id=ppo.eos_id,
                        chunk=ppo.decode_chunk, kv_layout=ppo.kv_layout,
                        block_size=ppo.kv_block_size,
                        prefix_cache=ppo.prefix_cache)
        # int8-KV experience generation: only the engine's view of the
        # model flips (cache dtypes + scale planes) — the actor params
        # it consumes and every training-side forward are unchanged
        gen_cfg = (self.actor_cfg.replace(kv_quant=True)
                   if ppo.kv_quant else self.actor_cfg)
        # disaggregated mode: generation runs on its OWN mesh — the
        # engine (and its KV layout) binds to the rollout devices, and
        # params arrive there via the WeightPublisher instead of the
        # per-iteration to_inference reshard
        self.rollout_mesh = rollout_mesh
        if rollout_mesh is not None:
            rm = (rollout_mesh if int(np.prod(
                list(rollout_mesh.shape.values()))) > 1 else None)
            self.gen_engine = GenerationEngine(gen_cfg, mesh=rm,
                                               **gen_opts)
        else:
            self.gen_engine = (engine.generation_engine(cfg=gen_cfg,
                                                        **gen_opts)
                               if engine is not None
                               else GenerationEngine(gen_cfg, **gen_opts))
        # each program runs under a name of its own (``jit_<name>`` on a
        # profiler trace's XLA Modules line)
        a_step = obs.named("ppo_actor_step", partial(actor_step, actor_cfg,
                                                     ppo))
        c_step = obs.named("ppo_critic_step", partial(critic_step,
                                                      critic_cfg, ppo))
        self._mk_exp = jax.jit(obs.named(
            "ppo_make_experience",
            partial(make_experience, actor_cfg, critic_cfg, ppo)))
        if self._multi:
            # jit the PPO step AGAINST the mesh: the state pins back to
            # the training layout every step (one compile across steps —
            # input layouts are committed by device_put), metrics come
            # back replicated
            a_sh = engine.train_state_shardings(self.actor_cfg)
            c_sh = engine.train_state_shardings(
                self.critic_cfg, specs=R.param_specs(self.critic_cfg))
            rep = NamedSharding(self.mesh, P())
            self._actor_step = jax.jit(a_step, out_shardings=(a_sh, rep))
            self._critic_step = jax.jit(c_step, out_shardings=(c_sh, rep))
        else:
            # the state is rebound from the step's result: donate it
            donate = (0,) if self._donate else ()
            self._actor_step = jax.jit(a_step, donate_argnums=donate)
            self._critic_step = jax.jit(c_step, donate_argnums=donate)
        # staleness telemetry (async mode, lag > 0 only)
        self._guard = jax.jit(obs.named(
            "ppo_staleness_guard", partial(staleness_guard_stats, actor_cfg)))

    # -------------------------------------------------------------- #
    def _mesh_ctx(self):
        """Active-mesh context for tracing `PartitionSpec`-based
        constraints (no-op single-device)."""
        return self.mesh if self._multi else contextlib.nullcontext()

    def _shard_batch(self, tree):
        """Commit a batch pytree to the data axis (leading dim) when the
        mesh is multi-device and the batch divides it; replicate
        otherwise.  Stable input layouts = no retrace across steps."""
        if not self._multi or tree is None:
            return tree
        from repro.sharding import strategy as S
        return S.shard_batch(tree, self.mesh)

    # -------------------------------------------------------------- #
    def generate_experience(self, prompts, key):
        """Inference phase: one Hybrid-Engine reshard to the TP layout,
        then the serving-grade engine decodes.

        ``prompts`` is either a fixed-shape ``(B, Lp)`` token array —
        the batched early-exit decode path, token-identical to the
        fixed-scan reference — or a list of
        :class:`repro.serving.engine.Request` with ragged prompts and
        per-request :class:`~repro.serving.engine.SamplingParams`, which
        runs through the request-level engine core (continuous batching;
        freed KV slots are refilled mid-batch) and is scored at each
        sequence's true length via the attention mask."""
        if isinstance(prompts, (list, tuple)):
            return self._experience_from_requests(list(prompts), key)
        rollout, gm = self.generate_rollout(prompts, key)
        exp, sm = self.score_rollout(rollout)
        return exp, {**gm, **sm}

    # ---------------- rollout / scoring split (async seam) --------- #
    def generate_rollout(self, prompts, key, *, gen_params=None,
                         version: int = 0):
        """Generation phase only: decode a fixed-shape prompt batch into
        a version-tagged :class:`~repro.core.replay.RolloutBatch`, no
        scoring.  ``gen_params`` are params ALREADY in the generation
        layout (the async WeightPublisher's push); when ``None`` the
        sync reshard path runs (``to_inference`` on the hybrid engine,
        or a cross-mesh put when a rollout mesh is configured)."""
        from repro.core.replay import RolloutBatch
        with obs.span("ppo.generate_rollout") as sp:
            if self.ppo.n_samples_per_prompt > 1:
                # best-of-n on the fixed-shape path: tile each prompt row
                # n times (rows sample independently from the shared key,
                # so stochastic copies diverge; the request path
                # additionally reuses each prompt's prefill via the
                # prefix cache)
                prompts = jnp.repeat(jnp.asarray(prompts),
                                     self.ppo.n_samples_per_prompt, axis=0)
            params = gen_params
            if params is None:
                params = self.actor.params
                if self.rollout_mesh is not None:
                    from repro.sharding.strategy import cross_mesh_put
                    params = cross_mesh_put(params,
                                            self.publish_shardings())
                elif self.engine is not None:
                    params = self.engine.to_inference(params)
            # the sampled tokens are on the host when generate returns
            out = self.gen_engine.generate(params, prompts, key)
        st = self.gen_engine.last_stats
        n_gen = float(st["generated_tokens"])
        gm = {"gen_len": n_gen / out["response_mask"].shape[0],
              "gen_tok_s": n_gen / max(sp.seconds, 1e-9),
              "decode_steps": float(st["decode_steps"])}
        if gen_params is None:
            self._add_reshard_metrics(gm)
        return RolloutBatch(sequences=out["sequences"],
                            response_mask=out["response_mask"],
                            attn_mask=None, version=version), gm

    def score_rollout(self, rollout, *, behavior_params=None,
                      policy_lag: Optional[int] = None):
        """Scoring phase: behavior logprobs, ref logprobs, values,
        reward, KL-shaped rewards, GAE — the same jitted graph for sync
        and async, which is what keeps lockstep bit-identical.

        ``behavior_params`` is the policy that actually SAMPLED the
        rollout (the publisher's retained train-layout tree for the
        rollout's version tag); defaulting to the current actor is the
        on-policy/sync case.  Scoring with the behavior weights makes
        ``exp.logprobs`` the exact sampling-time logprobs, so the PPO
        importance ratio is exact — recomputing from a since-updated
        actor would silently report ratio == 1 and hide staleness.

        ``policy_lag`` (consumer version minus rollout version), when
        given, emits the staleness-guard metrics ``policy_lag`` /
        ``is_ratio_mean`` / ``is_ratio_max``; the guard forward runs
        only when lag > 0."""
        behavior = (behavior_params if behavior_params is not None
                    else self.actor.params)
        if rollout.attn_mask is None:
            seqs, mask = self._shard_batch(
                (jnp.asarray(rollout.sequences),
                 jnp.asarray(rollout.response_mask)))
            extra = ()
        else:
            seqs, mask, attn = self._shard_batch(
                (jnp.asarray(rollout.sequences),
                 jnp.asarray(rollout.response_mask),
                 jnp.asarray(rollout.attn_mask)))
            extra = (attn,)
        with obs.span("ppo.score_rollout"):
            with obs.span("ppo.make_experience"), self._mesh_ctx():
                exp, score, moe = self._mk_exp(behavior, self.ref_params,
                                               self.critic.params,
                                               self.reward_params, seqs,
                                               mask, *extra)
            with obs.span("ppo.score_read"):
                mean, moe = jax.device_get((score.mean(), moe))
                sm = {"reward_score": float(mean)}
                cfgs = {"actor": self.actor_cfg, "ref": self.actor_cfg,
                        "critic": self.critic_cfg,
                        "reward": self.critic_cfg}
                for role, c in moe.items():
                    count_moe("score", role, cfgs[role], seqs.size, c)
        if policy_lag is not None:
            sm["policy_lag"] = float(policy_lag)
            if policy_lag > 0:
                with self._mesh_ctx():
                    g = self._guard(self.actor.params, exp.sequences,
                                    exp.logprobs, exp.mask)
                sm["is_ratio_mean"] = float(g["is_ratio_mean"])
                sm["is_ratio_max"] = float(g["is_ratio_max"])
            else:
                # on-policy: the ratio is identically 1 by construction
                sm["is_ratio_mean"] = 1.0
                sm["is_ratio_max"] = 1.0
        return exp, sm

    def publish_shardings(self):
        """Target layout for async weight publication: the rollout
        mesh's inference (TP) layout when one is configured, the hybrid
        engine's inference layout on a shared multi-device mesh, or
        ``None`` (zero-copy reference sharing) single-device."""
        if self.rollout_mesh is not None:
            from repro.sharding import strategy as S
            return S.param_shardings(self.actor_cfg, self.rollout_mesh,
                                     "tp")
        if self._multi:
            return self.engine.infer_shardings
        return None

    def _expand_samples(self, requests):
        """Best-of-n expansion: replicate each request
        ``n_samples_per_prompt`` times under fresh internal uids, copies
        of one prompt adjacent in the queue (the first copy's admission
        indexes the prompt's KV blocks, so with the paged engine's
        prefix cache every later copy prefills only the final token
        chunk).  Seeded requests get per-copy seeds — identical samples
        per prompt would make best-of-n pointless."""
        n = self.ppo.n_samples_per_prompt
        if n <= 1:
            return list(requests)
        out = []
        for i, r in enumerate(requests):
            for j in range(n):
                p = r.params
                if p is not None and p.seed is not None and j > 0:
                    p = dataclasses.replace(p, seed=p.seed + j)
                out.append(dataclasses.replace(r, uid=i * n + j, params=p))
        return out

    def _experience_from_requests(self, requests, key, *, slots: int = 8):
        """Ragged experience generation through the stepwise engine core:
        serve the request queue (continuous batching over ragged
        prompts/budgets; each prompt sampled ``n_samples_per_prompt``
        times), then right-pad ``prompt | generated | pad`` rows to one
        stable width for the jitted scorer.  Padding is excluded from
        the response mask and from the reward model's end-score position
        via the attention mask."""
        eng = self.gen_engine
        with obs.span("ppo.generate_requests") as sp:
            params = self.actor.params
            if self.engine is not None:
                params = self.engine.to_inference(params)
            requests = self._expand_samples(requests)
            outs = {c.uid: c for c in eng.serve(
                params, requests, key, slots=min(slots, len(requests)))}
        # stable width across PPO iterations with a fixed budget/geometry
        W = max(len(r.tokens) + eng.resolve(r)[3] for r in requests)
        B = len(requests)
        pad_tok = eng.eos_id if eng.eos_id is not None else 0
        seqs = np.full((B, W), pad_tok, np.int32)
        resp = np.zeros((B, W), bool)
        attn = np.zeros((B, W), np.float32)
        for i, r in enumerate(requests):
            c = outs[r.uid]
            Lp, n = len(c.prompt), int(c.tokens.size)
            seqs[i, :Lp] = c.prompt
            seqs[i, Lp:Lp + n] = c.tokens
            resp[i, Lp:Lp + n] = True
            attn[i, :Lp + n] = 1.0
        from repro.core.replay import RolloutBatch
        response_mask = jnp.asarray(resp)
        n_gen = float(response_mask.sum())
        rollout = RolloutBatch(sequences=jnp.asarray(seqs),
                               response_mask=response_mask,
                               attn_mask=jnp.asarray(attn))
        exp, sm = self.score_rollout(rollout)
        gm = {**sm,
              "gen_len": float(response_mask.sum(1).mean()),
              "gen_tok_s": n_gen / max(sp.seconds, 1e-9),
              "decode_steps": float(eng.last_stats["decode_steps"])}
        if "prefill_hit_rate" in eng.last_stats:     # paged engine
            gm["prefill_hit_rate"] = float(
                eng.last_stats["prefill_hit_rate"])
        self._add_reshard_metrics(gm)
        return exp, gm

    def _add_reshard_metrics(self, gm: dict) -> None:
        """Surface the MEASURED Hybrid-Engine phase-transition cost (wall
        time + bytes read off the resharded arrays) in the experience
        metrics."""
        if self.engine is None:
            return
        rs = getattr(self.engine, "last_reshard_stats", {})
        gm["reshard_bytes"] = float(rs.get("gathered_bytes", 0))
        gm["reshard_s"] = float(rs.get("seconds", 0.0))

    # ---------------------- checkpoint seam ----------------------- #
    def state_tree(self):
        """The trainer's full durable state as ONE pytree: actor and
        critic TrainStates (params + fp32 Adam moments + step counters)
        and the EMA shadow.  What the fault-tolerant checkpointer saves
        and what :meth:`load_state_tree` restores."""
        return {"actor": self.actor, "critic": self.critic,
                "ema": self.ema}

    def state_shardings(self):
        """NamedShardings matching :meth:`state_tree` in the training
        layout (``None`` single-device) — a restore commits straight to
        this mesh's layout regardless of the topology the checkpoint
        was saved under."""
        if not self._multi:
            return None
        a_sh = self.engine.train_state_shardings(self.actor_cfg)
        c_sh = self.engine.train_state_shardings(
            self.critic_cfg, specs=R.param_specs(self.critic_cfg))
        return {"actor": a_sh, "critic": c_sh,
                "ema": a_sh.params if self.ema is not None else None}

    def load_state_tree(self, tree):
        """Adopt a restored state tree (host arrays or committed
        jax arrays), placing it into the mesh's training layout."""
        sh = self.state_shardings()
        if sh is not None:
            tree = jax.device_put(tree, sh)
        self.actor = tree["actor"]
        self.critic = tree["critic"]
        self.ema = tree["ema"]

    def train_rlhf(self, exp: X.Experience, ptx_batch=None):
        """Training phase (the mesh's ZeRO/TP layout when one is
        configured: the experience batch is committed to the data axis,
        the updated TrainStates pin back to the training layout)."""
        exp = self._shard_batch(exp)
        ptx_batch = self._shard_batch(ptx_batch)
        with obs.span("ppo.train_rlhf"):
            with self._mesh_ctx():
                with obs.span("ppo.actor_step"):
                    self.actor, am = self._actor_step(self.actor, exp,
                                                      ptx_batch)
                with obs.span("ppo.critic_step"):
                    self.critic, cm = self._critic_step(self.critic, exp)
            if self.ema is not None:
                with obs.span("ema.update"):
                    self.ema = EMA.update(self.ema, self.actor.params,
                                          self.ppo.ema_decay)
            # the host's wait for the device: the step metrics' values
            with obs.span("ppo.train_read"):
                am, cm = jax.device_get((am, cm))
                for role, cfg, m in (("actor", self.actor_cfg, am),
                                     ("critic", self.critic_cfg, cm)):
                    if MOE_COUNTS in m:
                        count_moe("train", role, cfg, exp.sequences.size,
                                  m.pop(MOE_COUNTS))
                return {**{k: float(v) for k, v in am.items()},
                        **{k: float(v) for k, v in cm.items()}}

    def ema_params(self):
        return EMA.to_params(self.ema, self.actor.params)
