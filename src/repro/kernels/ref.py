"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal=True, window=None):
    """q: (B, KV, G, Lq, D); k, v: (B, KV, Lk, D) -> (B, KV, G, Lq, D)."""
    B, KV, G, Lq, D = q.shape
    Lk = k.shape[2]
    s = jnp.einsum("bkgqd,bksd->bkgqs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / np.sqrt(D)
    qpos = jnp.arange(Lq) + (Lk - Lq)      # aligned to the end of k
    kpos = jnp.arange(Lk)
    mask = jnp.ones((Lq, Lk), bool)
    if causal:
        mask &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        mask &= (qpos[:, None] - kpos[None, :]) < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksd->bkgqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def _valid(lens, S):
    return jnp.arange(S)[None, :] < lens[:, None]


def decode_attention_ref(q, k_cache, v_cache, lens):
    """q: (B, KV, G, D); caches: (B, KV, S, D); lens: (B,) valid-row
    counts."""
    D = q.shape[-1]
    valid = _valid(lens, k_cache.shape[2])
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / np.sqrt(D)
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v_cache.astype(jnp.float32))
    return o.astype(q.dtype)


def _gather_blocks(pool, block_tables):
    """(nblocks, KV, bs, ...) pool -> (B, KV, nb * bs, ...) dense cache."""
    B, nb = block_tables.shape
    g = jnp.moveaxis(pool[block_tables], 2, 1)    # (B, KV, nb, bs, ...)
    return g.reshape((B, g.shape[1], nb * g.shape[3]) + g.shape[4:])


def paged_decode_attention_ref(q, k_pool, v_pool, block_tables, lens):
    """q: (B, KV, G, D); pools: (nblocks, KV, bs, D); block_tables:
    (B, nb) int32; lens: (B,) int32.  Gathers each sequence's blocks
    into a dense virtual cache and reuses the dense decode oracle."""
    return decode_attention_ref(q, _gather_blocks(k_pool, block_tables),
                                _gather_blocks(v_pool, block_tables), lens)


def decode_attention_quant_ref(q, k_cache, v_cache, k_scale, v_scale,
                               lens):
    """Int8-KV decode oracle.  q: (B, KV, G, D) fp; caches: (B, KV, S, D)
    int8; scales: (B, KV, S) fp32; lens: (B,) valid-row counts.  Mirrors
    the fused kernel's algebra: scales are applied to the
    score/probability matrices, never to a dequantized K/V copy."""
    D = q.shape[-1]
    valid = _valid(lens, k_cache.shape[2])
    s = jnp.einsum("bkgd,bksd->bkgs", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32)) / np.sqrt(D)
    s = s * k_scale.astype(jnp.float32)[:, :, None, :]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = p * v_scale.astype(jnp.float32)[:, :, None, :]
    o = jnp.einsum("bkgs,bksd->bkgd", p, v_cache.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_decode_attention_quant_ref(q, k_pool, v_pool, k_scale, v_scale,
                                     block_tables, lens):
    """Int8-KV paged decode oracle.  q: (B, KV, G, D) fp; pools:
    (nblocks, KV, bs, D) int8; scale pools: (nblocks, KV, bs) fp32;
    block_tables: (B, nb) int32; lens: (B,) int32.  Gathers blocks and
    scale rows into a dense virtual cache and reuses the dense oracle."""
    return decode_attention_quant_ref(
        q, _gather_blocks(k_pool, block_tables),
        _gather_blocks(v_pool, block_tables),
        _gather_blocks(k_scale, block_tables),
        _gather_blocks(v_scale, block_tables), lens)


def rmsnorm_ref(x, w, eps=1e-5):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def ssd_intra_ref(X, dt, A, B, C):
    """Intra-chunk SSD reference.

    X: (b, nc, q, h, p)  dt: (b, nc, q, h)  A: (h,)  B, C: (b, nc, q, n)
    Returns (Y_diag (b,nc,q,h,p), S_c (b,nc,h,p,n), chunk_decay (b,nc,h),
             A_cs (b,nc,h,q)).
    """
    dA = jnp.moveaxis(dt * A[None, None, None, :], 3, 2)   # (b,nc,h,q)
    Xd = X * dt[..., None]
    A_cs = jnp.cumsum(dA, -1)
    qlen = dA.shape[-1]
    d = A_cs[..., :, None] - A_cs[..., None, :]
    mask = jnp.tril(jnp.ones((qlen, qlen), bool))
    Ldec = jnp.where(mask, jnp.exp(d), 0.0)
    scores = jnp.einsum("bcqn,bckn->bcqk", C.astype(jnp.float32),
                        B.astype(jnp.float32))
    Y_diag = jnp.einsum("bcqk,bchqk,bckhp->bcqhp", scores, Ldec,
                        Xd.astype(jnp.float32))
    decay_states = jnp.exp(A_cs[..., -1:] - A_cs)
    S_c = jnp.einsum("bchq,bcqn,bcqhp->bchpn", decay_states,
                     B.astype(jnp.float32), Xd.astype(jnp.float32))
    chunk_decay = jnp.exp(A_cs[..., -1])
    return (Y_diag.astype(X.dtype), S_c, chunk_decay, A_cs)
