"""Plain reference of the decoder both configurations run, written from
the published block description and independent of the system under
test: pre-norm RMSNorm, grouped-query attention with rotary positions
(pairs of adjacent features rotated), causal softmax, SwiGLU MLP, a
final RMSNorm, and a vocabulary head (or a scalar value head).

Everything is float32 with matmuls at ``Precision.HIGHEST`` (mode
``"f32"``).  Mode ``"fp8"`` is the same mathematics with every matmul's
operands rounded to float8 (e4m3 forward, e5m2 for the cotangent in the
backward, one scale per tensor): the next precision below the bfloat16
compute that the configurations state, used as the control that a sound
comparison must reject.

Besides the forward, the module gives what the harness asks of every
reference (``bench/lib/weights.py``, ``bench/lib/flops.py``): ``init``,
the random tree of one model in the layout the system consumes (layers
stacked on a leading axis), which the reference reads by key; and
``fwd_flops``, the forward FLOPs its layers require.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from bench.lib.flops import causal_pairs

HIGHEST = jax.lax.Precision.HIGHEST
_FP8 = {"e4m3": (jnp.float8_e4m3fn, 448.0), "e5m2": (jnp.float8_e5m2, 57344.0)}


def _fake_quant(x, fmt):
    dtype, fmax = _FP8[fmt]
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / fmax, 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum_fp8(spec, a, b):
    return _einsum(spec, _fake_quant(a, "e4m3"), _fake_quant(b, "e4m3"))


def _einsum_fp8_fwd(spec, a, b):
    qa, qb = _fake_quant(a, "e4m3"), _fake_quant(b, "e4m3")
    return _einsum(spec, qa, qb), (qa, qb)


def _einsum_fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y), qa, qb)
    return vjp(_fake_quant(g, "e5m2"))


_einsum_fp8.defvjp(_einsum_fp8_fwd, _einsum_fp8_bwd)


def dot(spec, a, b, mode):
    if mode == "f32":
        return _einsum(spec, a, b)
    if mode == "fp8":
        return _einsum_fp8(spec, a, b)
    raise ValueError(f"mode {mode!r}")


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (B, T, heads, hd); rotates feature pairs (2i, 2i+1) by
    position * theta**(-2i/hd)."""
    T, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # (T, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def attention(q, k, v, mode):
    """Causal grouped-query attention. q: (B,T,H,hd); k, v: (B,T,KV,hd);
    query head h reads key/value head h // (H // KV)."""
    B, T, H, hd = q.shape
    KV = k.shape[2]
    q = q.reshape(B, T, KV, H // KV, hd)
    s = dot("btkgd,bskd->bkgts", q, k, mode) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = dot("bkgts,bskd->btkgd", p, v, mode)
    return o.reshape(B, T, H * hd)


def layer(x, p, m, mode):
    B, T, _ = x.shape
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    eps, theta = m["rms_eps"], m["rope_theta"]
    a = p["attn"]
    h = rmsnorm(x, p["ln1"], eps)
    q = dot("btd,de->bte", h, a["wq"], mode).reshape(B, T, H, hd)
    k = dot("btd,de->bte", h, a["wk"], mode).reshape(B, T, KV, hd)
    v = dot("btd,de->bte", h, a["wv"], mode).reshape(B, T, KV, hd)
    o = attention(rope(q, theta), rope(k, theta), v, mode)
    x = x + dot("bte,ed->btd", o, a["wo"], mode)
    h = rmsnorm(x, p["ln2"], eps)
    mp = p["mlp"]
    g = dot("btd,df->btf", h, mp["w_gate"], mode)
    u = dot("btd,df->btf", h, mp["w_up"], mode)
    return x + dot("btf,fd->btd", jax.nn.silu(g) * u, mp["w_down"], mode)


def hidden(params, tokens, m, mode="f32"):
    """Final normed hidden states (B, T, D) of ``tokens`` (B, T)."""
    x = jnp.take(params["embed"], tokens, axis=0)
    stacked = params["segments"][0][0]

    def body(x, p):
        return layer(x, p, m, mode), None

    x, _ = jax.lax.scan(body, x, stacked)
    return rmsnorm(x, params["final_norm"], m["rms_eps"])


def logits(params, h, mode="f32"):
    return dot("btd,dv->btv", h, params["lm_head"], mode)


def values(params, h, mode="f32"):
    return dot("btd,dv->btv", h, params["v_head"], mode)[..., 0]


# ---------------------------------------------------------------- #
# weights: one model's tree, made on the device from a key
# ---------------------------------------------------------------- #
def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(
        jnp.float32(fan_in))


def init(key, m, head: str):
    """One decoder's tree; ``head`` is ``"lm"`` (vocabulary logits) or
    ``"value"`` (a scalar per position: critic and reward models).
    Matrices are normal with standard deviation ``1/sqrt(fan_in)``; norm
    weights are ones."""
    L, D, H, KV, hd, F, V = (m["n_layers"], m["d_model"], m["n_heads"],
                             m["n_kv_heads"], m["head_dim"], m["d_ff"],
                             m["vocab_size"])
    ks = iter(jax.random.split(key, 10))
    layer = {
        "ln1": jnp.ones((L, D), jnp.float32),
        "attn": {"wq": _normal(next(ks), (L, D, H * hd), D),
                 "wk": _normal(next(ks), (L, D, KV * hd), D),
                 "wv": _normal(next(ks), (L, D, KV * hd), D),
                 "wo": _normal(next(ks), (L, H * hd, D), H * hd)},
        "ln2": jnp.ones((L, D), jnp.float32),
        "mlp": {"w_gate": _normal(next(ks), (L, D, F), D),
                "w_up": _normal(next(ks), (L, D, F), D),
                "w_down": _normal(next(ks), (L, F, D), F)},
    }
    tree = {"embed": _normal(next(ks), (V, D), V),
            "segments": ((layer,),),
            "final_norm": jnp.ones((D,), jnp.float32)}
    if head == "lm":
        tree["lm_head"] = _normal(next(ks), (D, V), D)
    else:
        tree["v_head"] = _normal(next(ks), (D, 1), D)
    return tree


# ---------------------------------------------------------------- #
# required FLOPs of the layers (a multiply-add is 2 FLOPs)
# ---------------------------------------------------------------- #
def body_params(m) -> int:
    """Matmul weights of the decoder layers (attention and SwiGLU MLP)."""
    D, H, KV, hd, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["head_dim"], m["d_ff"])
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    return m["n_layers"] * per_layer


def attn_flops(m, pairs: int) -> int:
    """Scores and weighted values over ``pairs`` query-key pairs, all
    layers and heads: 2 matmuls of ``head_dim`` each, 2 FLOPs a MAC."""
    return 4 * m["n_layers"] * m["n_heads"] * m["head_dim"] * pairs


def fwd_flops(m, n_tokens: int, n_ctx: int | None = None) -> int:
    """Forward FLOPs of the layers for ``n_tokens`` new tokens that follow
    ``n_ctx - n_tokens`` tokens already in the cache (causal)."""
    n_ctx = n_tokens if n_ctx is None else n_ctx
    return (2 * n_tokens * body_params(m)
            + attn_flops(m, causal_pairs(n_tokens, n_ctx)))
