"""Plain reference of DeepSeek-V2's decoder as one chip's share of an
expert-parallel deployment, written from the published description
(arXiv:2405.04434 and the model's ``config.json``) and independent of the
system under test:

- pre-norm RMSNorm blocks; a final RMSNorm; a vocabulary head (or a
  scalar value head);
- multi-head latent attention without a query LoRA: q = x Wq split into
  128 "nope" and 64 rope features per head; the compressed KV
  c = RMSNorm(x W_dkv[:, :512]) and one shared 64-wide rope key
  x W_dkv[:, 512:]; k_nope = c W_uk and v = c W_uv per head; causal
  softmax over q.k (192 features) at scale mscale^2 / sqrt(192);
- RoPE with YaRN: inverse frequencies blended between the interpolated
  (/ factor) and the extrapolated ones by a linear ramp over the
  correction range that ``beta_fast`` and ``beta_slow`` give over the
  ``original`` positions; cos and sin scaled by
  mscale(factor, mscale) / mscale(factor, mscale_all_dim);
- the first ``n_dense_layers`` layers with a SwiGLU MLP of width
  ``d_ff``; the rest with a MoE MLP: a softmax router over
  ``router_experts`` outputs, greedy top-k, gates renormalised only with
  ``norm_topk``, the ``n_experts`` experts held here (the first ones),
  each run over every token and weighted by the gate that token gave it
  (nought for a token that did not choose it), plus the shared experts.
  What experts held elsewhere would add is left out, as the program
  leaves it out.

Float32 with matmuls at ``Precision.HIGHEST`` (mode ``"f32"``); mode
``"fp8"`` rounds every matmul's operands to float8, the control
(``dense_decoder.dot``).  ``init`` gives one model's tree in the layout
the system consumes: ``segments`` holds the dense layers, then the MoE
layers, each stacked on a leading axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.flops import causal_pairs
from bench.references.dense_decoder import _normal, dot, rmsnorm


# ---------------------------------------------------------------- #
# YaRN
# ---------------------------------------------------------------- #
def yarn_mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 and m else 1.0


def inv_freq(m, dim):
    """RoPE's inverse frequencies over ``dim`` features, YaRN-blended."""
    base = m["rope_theta"]
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    f = m.get("yarn_factor", 0.0)
    if not f:
        return extra
    L = m["yarn_original_len"]

    def corr_dim(rot):
        return dim * math.log(L / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr_dim(m["yarn_beta_fast"])), 0)
    high = min(math.ceil(corr_dim(m["yarn_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (extra / f) * ramp + extra * (1.0 - ramp)


def rope_scale(m):
    f = m.get("yarn_factor", 0.0)
    return (yarn_mscale(f, m.get("yarn_mscale", 1.0))
            / yarn_mscale(f, m.get("yarn_mscale_all_dim", 0.0)))


def softmax_scale(m):
    d = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    f = m.get("yarn_factor", 0.0)
    return yarn_mscale(f, m.get("yarn_mscale_all_dim", 0.0)) ** 2 / math.sqrt(d)


def rope(x, m):
    """x: (B, T, ..., d); rotates feature pairs (2i, 2i+1) by
    position * inv_freq[i], cos and sin scaled by YaRN's factor."""
    T, d = x.shape[1], x.shape[-1]
    ang = (np.arange(T)[:, None] * inv_freq(m, d)[None]).astype(np.float32)
    shape = (1, T) + (1,) * (x.ndim - 3) + (d // 2,)
    s = rope_scale(m)
    cos = jnp.asarray(np.cos(ang) * s).reshape(shape)
    sin = jnp.asarray(np.sin(ang) * s).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


# ---------------------------------------------------------------- #
# layers
# ---------------------------------------------------------------- #
def mla(x, a, m, mode):
    B, T, _ = x.shape
    H, dn, dr, dv, r = (m["n_heads"], m["qk_nope_head_dim"],
                        m["qk_rope_head_dim"], m["v_head_dim"],
                        m["kv_lora_rank"])
    q = dot("btd,de->bte", x, a["wq"], mode).reshape(B, T, H, dn + dr)
    dkv = dot("btd,de->bte", x, a["w_dkv"], mode)
    c = rmsnorm(dkv[..., :r], a["kv_norm"], m["rms_eps"])
    k_rope = rope(dkv[..., r:], m)                          # (B, T, dr)
    k_nope = dot("btr,re->bte", c, a["w_uk"], mode).reshape(B, T, H, dn)
    v = dot("btr,re->bte", c, a["w_uv"], mode).reshape(B, T, H, dv)
    q_nope, q_rope = q[..., :dn], rope(q[..., dn:], m)
    s = (dot("bthd,bshd->bhts", q_nope, k_nope, mode)
         + dot("bthd,bsd->bhts", q_rope, k_rope, mode)) * softmax_scale(m)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = dot("bhts,bshd->bthd", jax.nn.softmax(s, -1), v, mode)
    return dot("bte,ed->btd", o.reshape(B, T, H * dv), a["wo"], mode)


def swiglu(x, p, mode):
    g = dot("...d,df->...f", x, p["w_gate"], mode)
    u = dot("...d,df->...f", x, p["w_up"], mode)
    return dot("...f,fd->...d", jax.nn.silu(g) * u, p["w_down"], mode)


def moe(x, p, m, mode):
    """The held experts' part of the layer plus the shared experts."""
    E, K = m["n_experts"], m["top_k"]
    probs = jax.nn.softmax(dot("btd,de->bte", x, p["router"], mode), -1)
    gate, idx = jax.lax.top_k(probs, K)                     # (B, T, K)
    if m.get("norm_topk", True):
        gate = gate / gate.sum(-1, keepdims=True)
    out = swiglu(x, p["shared"], mode)
    for e in range(E):
        w = jnp.sum(jnp.where(idx == e, gate, 0.0), -1)     # (B, T)
        ye = swiglu(x, {k: p[k][e] for k in ("w_gate", "w_up", "w_down")},
                    mode)
        out = out + w[..., None] * ye
    return out


def layer(x, p, m, mode, is_moe):
    eps = m["rms_eps"]
    x = x + mla(rmsnorm(x, p["ln1"], eps), p["attn"], m, mode)
    h = rmsnorm(x, p["ln2"], eps)
    return x + (moe(h, p["mlp"], m, mode) if is_moe
                else swiglu(h, p["mlp"], mode))


def hidden(params, tokens, m, mode="f32"):
    """Final normed hidden states (B, T, D) of ``tokens`` (B, T)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    for seg, is_moe in zip(params["segments"], _kinds(m)):
        def body(x, p, is_moe=is_moe):
            return layer(x, p, m, mode, is_moe), None
        x, _ = jax.lax.scan(body, x, seg[0])
    return rmsnorm(x, params["final_norm"], m["rms_eps"])


def logits(params, h, mode="f32"):
    return dot("btd,dv->btv", h, params["lm_head"], mode)


def values(params, h, mode="f32"):
    return dot("btd,dv->btv", h, params["v_head"], mode)[..., 0]


def _kinds(m):
    """Whether each segment's layers are MoE: dense first, if any."""
    k = m.get("n_dense_layers", 0)
    return ([False] if k else []) + [True]


def _counts(m):
    k = m.get("n_dense_layers", 0)
    return ([k] if k else []) + [m["n_layers"] - k]


# ---------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------- #
def init(key, m, head: str):
    """One model's tree; matrices normal with standard deviation
    1/sqrt(fan_in), norm weights ones."""
    D, H, V = m["d_model"], m["n_heads"], m["vocab_size"]
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    F, Fe, E, R = m["d_ff"], m["moe_d_ff"], m["n_experts"], m["router_experts"]
    Fs = m["n_shared_experts"] * Fe
    keys = iter(jax.random.split(key, 64))

    def mlp(L, width):
        return {"w_gate": _normal(next(keys), (L, D, width), D),
                "w_up": _normal(next(keys), (L, D, width), D),
                "w_down": _normal(next(keys), (L, width, D), width)}

    def block(L, is_moe):
        out = {
            "ln1": jnp.ones((L, D), jnp.float32),
            "attn": {"wq": _normal(next(keys), (L, D, H * (dn + dr)), D),
                     "w_dkv": _normal(next(keys), (L, D, r + dr), D),
                     "kv_norm": jnp.ones((L, r), jnp.float32),
                     "w_uk": _normal(next(keys), (L, r, H * dn), r),
                     "w_uv": _normal(next(keys), (L, r, H * dv), r),
                     "wo": _normal(next(keys), (L, H * dv, D), H * dv)},
            "ln2": jnp.ones((L, D), jnp.float32),
        }
        if is_moe:
            out["mlp"] = {
                "router": _normal(next(keys), (L, D, R), D),
                "w_gate": _normal(next(keys), (L, E, D, Fe), D),
                "w_up": _normal(next(keys), (L, E, D, Fe), D),
                "w_down": _normal(next(keys), (L, E, Fe, D), Fe),
                "shared": mlp(L, Fs)}
        else:
            out["mlp"] = mlp(L, F)
        return out

    tree = {"embed": _normal(next(keys), (V, D), V),
            "segments": tuple((block(n, k),) for n, k in
                              zip(_counts(m), _kinds(m))),
            "final_norm": jnp.ones((D,), jnp.float32)}
    if head == "lm":
        tree["lm_head"] = _normal(next(keys), (D, V), D)
    else:
        tree["v_head"] = _normal(next(keys), (D, 1), D)
    return tree


# ---------------------------------------------------------------- #
# required FLOPs of the layers (a multiply-add is 2 FLOPs)
# ---------------------------------------------------------------- #
def fwd_flops(m, n_tokens: int, n_ctx: int | None = None) -> int:
    """Forward FLOPs of the layers for ``n_tokens`` new tokens after
    ``n_ctx - n_tokens`` cached ones (causal): MLA's projections (the
    latent expanded to keys and values per token) and its scores over
    192 and values over 128 features per pair; the dense MLP; in each MoE
    layer the router, the shared experts and the held experts' share of
    the routed slots, ``n_tokens * top_k * n_experts / router_experts``
    slots of a SwiGLU expert."""
    n_ctx = n_tokens if n_ctx is None else n_ctx
    D, H = m["d_model"], m["n_heads"]
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    attn_w = D * H * (dn + dr) + D * (r + dr) + r * H * (dn + dv) + H * dv * D
    attn = (2 * n_tokens * attn_w
            + 2 * H * (dn + dr + dv) * causal_pairs(n_tokens, n_ctx))
    k = m.get("n_dense_layers", 0)
    dense = 2 * n_tokens * 3 * D * m["d_ff"]
    Fe = m["moe_d_ff"]
    held_slots = n_tokens * m["top_k"] * m["n_experts"] // m["router_experts"]
    moe = 2 * (n_tokens * (D * m["router_experts"]
                           + 3 * D * m["n_shared_experts"] * Fe)
               + held_slots * 3 * D * Fe)
    return m["n_layers"] * attn + k * dense + (m["n_layers"] - k) * moe
