"""Jit'd dispatch wrappers over the Pallas kernels.

These are the entry points the model layer calls when
``cfg.pallas_kernels`` (on a TPU by default); off-TPU they run the
kernels in ``interpret=True`` mode (Python execution of the kernel body)
so correctness is CPU-verifiable.  Layout adaptation between the model's
(B, L, H, D) convention and the kernels' grouped (B, KV, G, ...)
convention happens here, and so does the choice of TPU-legal tiles: the
last two dims of every block are the array's own or multiples of
(8, 128), so a length with no such divisor is zero-padded up to one and
the pad is masked inside the kernel.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import flash_attention_bwd as _fab
from repro.kernels import paged_attention as _paged
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rms
from repro.kernels import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tile(total: int, preferred: int, align: int) -> tuple[int, int]:
    """(block, padded_total) for one tiled axis: the whole axis when it
    fits in ``preferred``, else the largest multiple of ``align`` that
    divides it, else the axis zero-padded up to a multiple of ``align``.
    A ``preferred`` below ``align`` is raised to ``align``."""
    preferred = max(preferred, align)
    if total <= preferred:
        return total, total
    for blk in range(preferred - preferred % align, 0, -align):
        if total % blk == 0:
            return blk, total
    return _tile(-(-total // align) * align, preferred, align)


def _pad_axis(x, axis: int, size: int):
    pad = size - x.shape[axis]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def flash_attention(q, k, v, *, causal=True, window=None, q_block=256,
                    k_block=512, sm_scale=None):
    """Differentiable Pallas attention in the model layout.

    q: (B, L, H, D); k, v: (B, Lk, KV, D) -> (B, L, H, D).  Queries are
    the last L of the Lk key positions; ``sm_scale`` (default 1/sqrt(D))
    multiplies the scores.  The query tile is a multiple of
    128 (it is the lane dim of the backward's lse/delta blocks) and the
    key tile a multiple of 8, or the whole axis."""
    B, Lq, H, D = q.shape
    Lk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qb, Lq_p = _tile(Lq, q_block, 128)
    kb, Lk_p = _tile(Lk, k_block, 8)
    q5 = jnp.moveaxis(q.reshape(B, Lq, KV, G, D), 1, 3)   # (B,KV,G,Lq,D)
    k4 = jnp.moveaxis(k, 1, 2)                            # (B,KV,Lk,D)
    v4 = jnp.moveaxis(v, 1, 2)
    meta = (bool(causal), window, qb, kb, Lk - Lq, Lk, sm_scale)
    o = _flash_pallas(meta, _pad_axis(q5, 3, Lq_p), _pad_axis(k4, 2, Lk_p),
                      _pad_axis(v4, 2, Lk_p))[:, :, :, :Lq]
    return jnp.moveaxis(o, 3, 1).reshape(B, Lq, H, D)


def grouped_matmul(x, w, sizes):
    """Grouped matmul by the Pallas megablox kernel: x (M, K) rows sorted
    by group, w (G, K, N), sizes (G,) int32 rows per group, summing to at
    most M.  The kernel's grid visits only the row tiles that hold a
    group's rows, and reads the matrices of those groups alone, so rows
    past ``sizes.sum()`` and empty groups cost nothing (the rows' output
    is left undefined).  Tiles from :func:`gmm_tiling`.  Differentiable
    (the kernel's own VJP: grouped matmuls for dx, a transposed one for
    dw)."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    M = x.shape[0]
    tm, tk, tn = gmm_tiling(M, *w.shape[1:])
    M_p = -(-M // tm) * tm
    out = gmm(_pad_axis(x, 0, M_p), w, sizes.astype(jnp.int32), x.dtype,
              (tm, tk, tn), None, None, False, _interpret())
    return out[:M]


def gmm_tiling(M: int, K: int, N: int) -> tuple:
    """(tm, tk, tn) of :func:`grouped_matmul` for ``M`` rows times
    (K, N) matrices."""
    return (128 if M <= 1024 else 256, min(512, K), min(512, N))


def decode_block(S: int) -> int:
    """Sequence tile of the dense decode kernels: the lane dim of the
    int8 scale blocks, so a multiple of 128 or the whole slab."""
    if S <= 512:
        return S
    for blk in range(512, 0, -128):
        if S % blk == 0:
            return blk
    return S


def decode_attention(q, k_cache, v_cache, lens, layer):
    """q: (B, H, D); caches: the stacked arena (n, B, KV, S, D), already in
    the kernel's KV-head-major layout and read at layer ``layer``; lens:
    (B,) valid-row counts (rows ``0..lens-1`` of each slot are
    attended)."""
    B, H, D = q.shape
    KV, S = k_cache.shape[2], k_cache.shape[3]
    q4 = q.reshape(B, KV, H // KV, D)
    o = _dec.decode_attention_fwd(q4, k_cache, v_cache, lens, layer,
                                  s_block=decode_block(S),
                                  interpret=_interpret())
    return o.reshape(B, H, D)


def decode_attention_quant(q, k_cache, v_cache, k_scale, v_scale, lens,
                           layer):
    """Int8-KV decode.  q: (B, H, D) fp; caches: the stacked int8 arena
    (n, B, KV, S, D); scales: (n, B, KV, S) fp32; read at layer
    ``layer``; lens: (B,).  Dequant is fused into the kernel's online
    softmax — K/V tiles cross HBM as int8 bytes."""
    B, H, D = q.shape
    KV, S = k_cache.shape[2], k_cache.shape[3]
    q4 = q.reshape(B, KV, H // KV, D)
    o = _dec.decode_attention_quant_fwd(q4, k_cache, v_cache, k_scale,
                                        v_scale, lens, layer,
                                        s_block=decode_block(S),
                                        interpret=_interpret())
    return o.reshape(B, H, D)


def paged_decode_attention_quant(q, k_pool, v_pool, k_scale, v_scale,
                                 block_tables, lens, layer):
    """Int8-KV paged decode.  q: (B, H, D) fp; pools: the stacked
    (n, nblocks, KV, bs, D) int8 pool consumed without a transpose; scale
    pools: (n, nblocks, KV, bs) fp32 riding the same block-table
    indirection; all read at layer ``layer``; block_tables: (B, nb)
    int32; lens: (B,) int32."""
    B, H, D = q.shape
    KV = k_pool.shape[2]
    q4 = q.reshape(B, KV, H // KV, D)
    o = _paged.paged_decode_attention_quant_fwd(
        q4, k_pool, v_pool, k_scale, v_scale, block_tables, lens, layer,
        interpret=_interpret())
    return o.reshape(B, H, D)


def paged_decode_attention(q, k_pool, v_pool, block_tables, lens, layer):
    """q: (B, H, D); pools: the stacked (n, nblocks, KV, bs, D) pool — the
    model-side paged cache layout, consumed without a transpose (the
    kernel's BlockSpec takes one (bs, D) tile per KV head of layer
    ``layer`` straight out of the pool); block_tables: (B, nb) int32;
    lens: (B,) int32 valid-row counts."""
    B, H, D = q.shape
    KV = k_pool.shape[2]
    q4 = q.reshape(B, KV, H // KV, D)
    o = _paged.paged_decode_attention_fwd(q4, k_pool, v_pool, block_tables,
                                          lens, layer,
                                          interpret=_interpret())
    return o.reshape(B, H, D)


def rmsnorm(x, w, *, eps=1e-5):
    """Fused RMSNorm over the last axis.  Differentiable: the backward is
    the autodiff of the jnp reference (``ref.rmsnorm_ref``), recomputed
    from the saved input."""
    return _rmsnorm(eps, x, w)


def _rmsnorm_kernel(eps, x, w):
    shape = x.shape
    R = 1
    for d in shape[:-1]:
        R *= d
    rb, R_p = _tile(R, 256, 8)
    x2d = _pad_axis(x.reshape(R, shape[-1]), 0, R_p)
    o = _rms.rmsnorm_fwd(x2d, w, eps=eps, row_block=rb,
                         interpret=_interpret())
    return o[:R].reshape(shape)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rmsnorm(eps, x, w):
    return _rmsnorm_kernel(eps, x, w)


def _rmsnorm_fwd(eps, x, w):
    return _rmsnorm_kernel(eps, x, w), (x, w)


def _rmsnorm_bwd(eps, res, g):
    x, w = res
    _, vjp = jax.vjp(lambda x, w: _ref.rmsnorm_ref(x, w, eps), x, w)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def ssd_scan(X, dt, A, B, C, chunk, initial_state=None):
    """Full SSD: Pallas intra-chunk kernel + jnp inter-chunk recurrence.

    X: (b, l, h, p)  dt: (b, l, h)  A: (h,)  B, C: (b, l, n).
    Returns (Y (b,l,h,p), final_state (b,h,p,n)) — same contract as the
    jnp path in repro.models.modules.ssd_chunked.
    """
    b, l, h, p = X.shape
    n = B.shape[-1]
    q = min(chunk, l)
    nc = -(-l // q)
    pad = nc * q - l
    if pad:
        X = jnp.pad(X, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    Xc = X.reshape(b, nc, q, h, p)
    dtc = dt.reshape(b, nc, q, h)
    Bc = B.reshape(b, nc, q, n)
    Cc = C.reshape(b, nc, q, n)

    Y_diag, S_c, A_cs = _ssd.ssd_intra_fwd(Xc, dtc, A, Bc, Cc,
                                           interpret=_interpret())
    chunk_decay = jnp.exp(A_cs[..., -1])                # (b,nc,h)

    def step(s, xs):
        sc, dec = xs
        s_out = s
        s_next = s * dec[..., None, None] + sc
        return s_next, s_out

    s0 = (initial_state.astype(jnp.float32) if initial_state is not None
          else jnp.zeros((b, h, p, n), jnp.float32))
    final, states_in = jax.lax.scan(
        step, s0, (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    states_in = jnp.moveaxis(states_in, 0, 1)           # (b,nc,h,p,n)
    Y_off = jnp.einsum("bcqn,bchpn,bchq->bcqhp", Cc.astype(jnp.float32),
                       states_in, jnp.exp(A_cs))
    Y = (Y_diag.astype(jnp.float32) + Y_off).reshape(b, nc * q, h, p)[:, :l]
    return Y.astype(X.dtype), final


# ===================================================================== #
# Differentiable Pallas attention (fwd + bwd kernels, custom VJP) — the
# TPU training path behind ``flash_attention``.  Grouped layout: q
# (B, KV, G, Lq, D), k/v (B, KV, Lk, D).  ``meta`` = (causal, window,
# q_block, k_block, q_offset, lk_valid, sm_scale): query row 0 sits at
# absolute position q_offset, keys at or past lk_valid are padding, and
# sm_scale (None: 1/sqrt(D)) multiplies the scores.
# ===================================================================== #
def _fwd_with_lse(meta, q, k, v):
    """Forward kernel + lse recovery.  The fwd kernel keeps (m, l) in
    scratch; for the residual we recompute lse with the jnp oracle's
    blocked pass (cheap relative to bwd, avoids a second kernel output
    plumbing in interpret mode)."""
    causal, window, qb, kb, q_offset, lk_valid, sm_scale = meta
    out = _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                  q_block=qb, k_block=kb, q_offset=q_offset,
                                  lk_valid=lk_valid, sm_scale=sm_scale,
                                  interpret=_interpret())
    B, KV, G, Lq, D = q.shape
    from repro.models.modules import _flash_fwd_impl
    qm = jnp.moveaxis(q, 3, 1).reshape(B, Lq, KV * G, D)
    km = jnp.moveaxis(k[:, :, :lk_valid], 2, 1)
    vm = jnp.moveaxis(v[:, :, :lk_valid], 2, 1)
    _, lse = _flash_fwd_impl((causal, window, qb, min(kb, lk_valid),
                              q_offset, sm_scale), qm, km, vm)
    return out, lse


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash_pallas(meta, q, k, v):
    return _fwd_with_lse(meta, q, k, v)[0]


def _flash_pallas_fwd(meta, q, k, v):
    out, lse = _fwd_with_lse(meta, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_pallas_bwd(meta, res, g):
    causal, window, qb, kb, q_offset, lk_valid, sm_scale = meta
    q, k, v, out, lse = res
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), -1)
    dq, dk, dv = _fab.flash_attention_bwd(
        q, k, v, g, lse, delta, causal=causal, window=window,
        q_block=qb, k_block=kb, q_offset=q_offset, lk_valid=lk_valid,
        sm_scale=sm_scale, interpret=_interpret())
    return dq, dk, dv


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)
