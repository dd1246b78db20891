"""Plain reference of one PPO stage-3 iteration (InstructGPT /
DeepSpeed-Chat form), independent of the system under test.

Given the sequences a rollout sampled, it scores them (actor and frozen
reference log-probs, critic values, the reward model's score at the last
token), shapes per-token rewards with a KL penalty, runs GAE with
whitened advantages, takes one clipped-surrogate actor step and one
clipped-value critic step, each with global-norm gradient clipping and
Adam.  Models are the forward of the configuration's reference ``D``
(``hidden``, ``logits``, ``values``; ``dense_decoder`` for the OPT pair);
no EMA is read, so the compared numbers do not depend on ``use_ema``.
Work is done in blocks of rows so that float32 fits beside nothing else.

``hp`` holds the job's settings as the traffic file states them.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


class PPOReference:
    def __init__(self, D, actor_m, critic_m, hp, *, mode="f32", rows=2):
        self.D, self.am, self.cm, self.hp = D, actor_m, critic_m, hp
        self.mode, self.rows = mode, rows
        self._score = jax.jit(self._score_block)
        self._grads = jax.jit(self._grad_block)
        # params and moments are rebound every step: donate them
        self._adam = jax.jit(self._adam_impl, donate_argnums=(0, 2, 3))

    # -------------------------------------------------------------- #
    def _score_block(self, pa, pref, pc, pr, seq):
        D, mode = self.D, self.mode
        tgt = seq[:, 1:]
        ha = D.hidden(pa, seq, self.am, mode)
        lg = D.logits(pa, ha[:, :-1], mode)
        lse = jax.nn.logsumexp(lg, -1)
        logp = jnp.take_along_axis(lg, tgt[..., None], -1)[..., 0] - lse
        prob = jax.nn.softmax(lg, -1)
        entropy = lse - jnp.sum(prob * lg, -1)
        hr = D.hidden(pref, seq, self.am, mode)
        lr = D.logits(pref, hr[:, :-1], mode)
        ref_logp = (jnp.take_along_axis(lr, tgt[..., None], -1)[..., 0]
                    - jax.nn.logsumexp(lr, -1))
        vals = D.values(pc, D.hidden(pc, seq, self.cm, mode), mode)[:, :-1]
        score = D.values(pr, D.hidden(pr, seq, self.cm, mode), mode)[:, -1]
        return dict(logp=logp, ref_logp=ref_logp, values=vals, score=score,
                    entropy=entropy)

    def score(self, pa, pref, pc, pr, seqs, resp_mask):
        """Experience of one rollout: numpy arrays over the whole batch."""
        parts = [self._score(pa, pref, pc, pr, jnp.asarray(seqs[i:i + self.rows]))
                 for i in range(0, len(seqs), self.rows)]
        out = {k: np.concatenate([np.asarray(p[k]) for p in parts])
               for k in parts[0]}
        mask = np.asarray(resp_mask[:, 1:], np.float32)
        hp = self.hp
        r = -hp["kl_coef"] * (out["logp"] - out["ref_logp"]) * mask
        n_resp = mask.sum(1)
        last = mask.argmax(1) + np.maximum(n_resp - 1, 0)
        bonus = np.clip(out["score"], -hp["clip_reward"], hp["clip_reward"])
        r[np.arange(len(r)), last.astype(int)] += bonus * (n_resp > 0)
        adv = np.zeros_like(r)
        B, T = r.shape
        for b in range(B):
            a_next, v_next = 0.0, 0.0
            for t in range(T - 1, -1, -1):
                if not mask[b, t]:
                    continue
                delta = r[b, t] + hp["gamma"] * v_next - out["values"][b, t]
                a_next = delta + hp["gamma"] * hp["lam"] * a_next
                adv[b, t] = a_next
                v_next = out["values"][b, t]
        ret = adv + out["values"] * mask
        n = max(mask.sum(), 1.0)
        mean = (adv * mask).sum() / n
        var = (((adv - mean) ** 2) * mask).sum() / n
        out.update(mask=mask, returns=ret,
                   advantages=(adv - mean) / np.sqrt(var + 1e-8) * mask)
        return out

    # -------------------------------------------------------------- #
    def _grad_block(self, pa, pc, seq, logp_old, v_old, adv, ret, mask, n):
        D, mode, hp = self.D, self.mode, self.hp

        def actor_loss(p):
            h = D.hidden(p, seq, self.am, mode)
            lg = D.logits(p, h[:, :-1], mode)
            logp = (jnp.take_along_axis(lg, seq[:, 1:, None], -1)[..., 0]
                    - jax.nn.logsumexp(lg, -1))
            ratio = jnp.exp(logp - logp_old)
            l1 = -adv * ratio
            l2 = -adv * jnp.clip(ratio, 1 - hp["clip_eps"], 1 + hp["clip_eps"])
            return jnp.sum(jnp.maximum(l1, l2) * mask) / n

        def critic_loss(p):
            v = D.values(p, D.hidden(p, seq, self.cm, mode), mode)[:, :-1]
            vc = v_old + jnp.clip(v - v_old, -hp["value_clip"],
                                  hp["value_clip"])
            l = jnp.maximum((v - ret) ** 2, (vc - ret) ** 2)
            return 0.5 * jnp.sum(l * mask) / n

        la, ga = jax.value_and_grad(actor_loss)(pa)
        lc, gc = jax.value_and_grad(critic_loss)(pc)
        return la, ga, lc, gc

    def grads(self, pa, pc, seqs, exp, rows=None):
        """Losses and gradients of one step over the rows ``rows`` (all by
        default), the mean taken over those rows' response tokens."""
        idx = np.arange(len(seqs)) if rows is None else np.asarray(rows)
        n = jnp.float32(max(exp["mask"][idx].sum(), 1.0))
        la = lc = 0.0
        ga = gc = None
        for i in range(0, len(idx), self.rows):
            j = idx[i:i + self.rows]
            a, g1, c, g2 = self._grads(
                pa, pc, jnp.asarray(seqs[j]), jnp.asarray(exp["logp"][j]),
                jnp.asarray(exp["values"][j]),
                jnp.asarray(exp["advantages"][j]),
                jnp.asarray(exp["returns"][j]), jnp.asarray(exp["mask"][j]),
                n)
            la, lc = la + float(a), lc + float(c)
            ga = g1 if ga is None else jax.tree.map(jnp.add, ga, g1)
            gc = g2 if gc is None else jax.tree.map(jnp.add, gc, g2)
        return la, ga, lc, gc

    # -------------------------------------------------------------- #
    def _adam_impl(self, params, grads, m, v, step, lr):
        hp = self.hp
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, hp["grad_clip"] / jnp.maximum(gnorm, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        b1, b2, eps = hp["adam_b1"], hp["adam_b2"], hp["adam_eps"]
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
        bc1, bc2 = 1 - b1 ** step, 1 - b2 ** step
        params = jax.tree.map(
            lambda p, m_, v_: p - lr * (m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps),
            params, m, v)
        norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(g * g)), grads)
        return params, m, v, norms

    def adam(self, params, grads, state, lr):
        """One Adam step with global-norm clipping; ``state`` is
        ``(m, v, step)``; returns new params, state and the norm of each
        leaf of the clipped gradient the optimizer used."""
        m, v, step = state
        step += 1
        params, m, v, g = self._adam(params, grads, m, v, jnp.float32(step),
                                     jnp.float32(lr))
        return params, (m, v, step), g


def zeros_like(tree):
    return jax.tree.map(jnp.zeros_like, tree)
