"""Measured CPU microbenchmarks of the hot-path ops (jnp path vs Pallas
interpret path — interpret mode is a correctness vehicle, not a perf
claim; the jnp timings are the real CPU numbers)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.models.modules import decode_attention, flash_attention, rmsnorm


def _time(fn, *args, n=5):
    fn(*args)                      # compile
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n


def run():
    key = jax.random.PRNGKey(0)
    rows = []

    B, L, H, KV, D = 2, 512, 8, 2, 64
    q = jax.random.normal(key, (B, L, H, D))
    k = jax.random.normal(key, (B, L, KV, D))
    v = jax.random.normal(key, (B, L, KV, D))
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    t = _time(fa, q, k, v)
    flops = 4 * B * H * L * L * D / 2
    rows.append(("micro_flash_attn_512", t * 1e6,
                 f"{flops/t/1e9:.1f}_GFLOPs"))

    S = 4096
    qd = jax.random.normal(key, (B, H, D))
    kc = jax.random.normal(key, (1, B, KV, S, D))
    vc = jax.random.normal(key, (1, B, KV, S, D))
    lens = jnp.full((B,), S, jnp.int32)
    dec = jax.jit(lambda q, k, v, n: decode_attention(q, k, v, n, 0))
    t = _time(dec, qd, kc, vc, lens)
    bytes_ = 2 * B * S * KV * D * 4
    rows.append(("micro_decode_attn_4k", t * 1e6,
                 f"{bytes_/t/1e9:.1f}_GB/s_cache_read"))

    x = jax.random.normal(key, (4096, 1024))
    w = jnp.ones((1024,))
    rn = jax.jit(lambda x, w: rmsnorm(x, w))
    t = _time(rn, x, w)
    rows.append(("micro_rmsnorm_4Mx", t * 1e6,
                 f"{2*x.size*4/t/1e9:.1f}_GB/s"))
    return rows
