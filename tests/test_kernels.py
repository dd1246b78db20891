"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes
(interpret=True executes the Pallas kernel body in Python on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd
from repro.kernels.ssd_scan import ssd_intra_fwd
from repro.kernels import ops

KEY = jax.random.PRNGKey(42)


@pytest.mark.parametrize("B,KV,G,Lq,Lk,D,causal,win,qb,kb", [
    (2, 2, 2, 64, 64, 32, True, None, 32, 32),
    (1, 1, 4, 128, 128, 64, True, 48, 64, 64),
    (2, 3, 1, 32, 96, 16, True, None, 16, 32),
    (1, 2, 2, 64, 64, 32, False, None, 32, 16),
    (1, 1, 1, 16, 16, 128, True, None, 16, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(B, KV, G, Lq, Lk, D, causal, win, qb, kb,
                                dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, KV, G, Lq, D), dtype)
    k = jax.random.normal(k2, (B, KV, Lk, D), dtype)
    v = jax.random.normal(k3, (B, KV, Lk, D), dtype)
    o = flash_attention_fwd(q, k, v, causal=causal, window=win,
                            q_block=qb, k_block=kb, interpret=True)
    r = ref.flash_attention_ref(q, k, v, causal=causal, window=win)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=tol, atol=tol)


N_LAYERS = 3


def _lens_cases(B, S, sb, key):
    """Ragged valid-row counts, then the edges of the length axis: one row,
    a full tile, and the whole slab."""
    return [jax.random.randint(key, (B,), 1, S + 1)] + [
        jnp.full((B,), n, jnp.int32) for n in (1, sb, S)]


@pytest.mark.parametrize("B,KV,G,S,D,sb", [
    (2, 2, 2, 128, 32, 64),
    (1, 4, 1, 64, 64, 32),
    (3, 1, 8, 96, 16, 32),
    (1, 8, 4, 256, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_kernel(B, KV, G, S, D, sb, dtype):
    """The layer-indexed kernel reads every layer of a stacked arena where
    it lies, with ragged lengths and lengths at 1, at a tile boundary and
    at S."""
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = jax.random.normal(k1, (B, KV, G, D), dtype)
    kc = jax.random.normal(k2, (N_LAYERS, B, KV, S, D), dtype)
    vc = jax.random.normal(k3, (N_LAYERS, B, KV, S, D), dtype)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    for layer in range(N_LAYERS):
        for nv in _lens_cases(B, S, sb, k4):
            o = decode_attention_fwd(q, kc, vc, nv, jnp.int32(layer),
                                     s_block=sb, interpret=True)
            r = ref.decode_attention_ref(q, kc[layer], vc[layer], nv)
            np.testing.assert_allclose(np.asarray(o, np.float32),
                                       np.asarray(r, np.float32),
                                       rtol=tol, atol=tol)


# ------------------------------------------------------------------ #
# int8-KV fused-dequant kernels vs ref.py — GQA shapes, ragged lens,
# trash-block rows
# ------------------------------------------------------------------ #
def _quant_cache(key, shape):
    """Random int8 values + per-row scales shaped like a real quantized
    cache (scales ~ absmax/127 of unit-normal activations)."""
    k1, k2 = jax.random.split(key)
    xi = jax.random.randint(k1, shape, -127, 128, jnp.int32).astype(jnp.int8)
    scale = jax.random.uniform(k2, shape[:-1], jnp.float32, 0.5, 3.0) / 127.0
    return xi, scale


@pytest.mark.parametrize("B,KV,G,S,D,sb", [
    (2, 2, 2, 128, 32, 64),
    (1, 4, 1, 64, 64, 32),
    (3, 1, 8, 96, 16, 32),
    (1, 8, 4, 256, 128, 128),
])
def test_decode_attention_quant_kernel(B, KV, G, S, D, sb):
    from repro.kernels.decode_attention import decode_attention_quant_fwd
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = jax.random.normal(k1, (B, KV, G, D), jnp.float32)
    kc, ks = _quant_cache(k2, (N_LAYERS, B, KV, S, D))
    vc, vs = _quant_cache(k3, (N_LAYERS, B, KV, S, D))
    for layer in range(N_LAYERS):
        for nv in _lens_cases(B, S, sb, k4):
            o = decode_attention_quant_fwd(q, kc, vc, ks, vs, nv,
                                           jnp.int32(layer), s_block=sb,
                                           interpret=True)
            r = ref.decode_attention_quant_ref(q, kc[layer], vc[layer],
                                               ks[layer], vs[layer], nv)
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,KV,G,D,bs,nb,nblocks", [
    (2, 2, 2, 32, 8, 4, 12),
    (1, 1, 8, 64, 16, 2, 5),
    (3, 4, 1, 16, 8, 8, 40),
])
def test_paged_attention_quant_kernel(B, KV, G, D, bs, nb, nblocks):
    """Ragged lens mean trailing table entries point at the trash block
    (id 0, zero values AND zero scales) — those rows must contribute
    nothing, exactly like the fp paged kernel's masking."""
    from repro.kernels.paged_attention import paged_decode_attention_quant_fwd
    k1, k2, k3, k4, k5 = jax.random.split(KEY, 5)
    q = jax.random.normal(k1, (B, KV, G, D), jnp.float32)
    kp, ks = _quant_cache(k2, (nblocks, KV, bs, D))
    vp, vs = _quant_cache(k3, (nblocks, KV, bs, D))
    # trash block 0 as the allocator initializes it: all-zero
    kp = kp.at[0].set(0); ks = ks.at[0].set(0.0)
    vp = vp.at[0].set(0); vs = vs.at[0].set(0.0)
    lens = jax.random.randint(k5, (B,), 1, nb * bs + 1)
    tbl = jax.random.randint(k4, (B, nb), 1, nblocks)
    # entries past each sequence's allocated prefix -> trash block
    nb_used = -(-lens[:, None] // bs)               # ceil-div, (B,1)
    tbl = jnp.where(jnp.arange(nb)[None] < nb_used, tbl, 0)
    # the pool as the second layer of a stacked pool, read where it lies
    stack = lambda x: jnp.stack([jnp.zeros_like(x), x])
    o = paged_decode_attention_quant_fwd(
        q, stack(kp), stack(vp), stack(ks), stack(vs), tbl, lens,
        jnp.int32(1), interpret=True)
    r = ref.paged_decode_attention_quant_ref(q, kp, vp, ks, vs, tbl, lens)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                               rtol=1e-4, atol=1e-4)


def test_ops_quant_adapters_match_jnp_paths():
    """ops.decode_attention_quant / paged_decode_attention_quant accept
    the model's stacked caches and match the jnp model paths in
    repro.models.modules (the use_pallas dispatch contract)."""
    from repro.models.modules import (decode_attention_paged_quant,
                                      decode_attention_quant)
    k1, k2, k3 = jax.random.split(KEY, 3)
    B, H, KV, D, S = 2, 4, 2, 16, 32
    qd = jax.random.normal(k1, (B, H, D))
    # model layout: a stacked (n, B, KV, S, D) arena, (n, B, KV, S) scales
    kc, ks = _quant_cache(k2, (2, B, KV, S, D))
    vc, vs = _quant_cache(k3, (2, B, KV, S, D))
    lens = jnp.asarray([S, 19])
    for layer in range(2):
        o = ops.decode_attention_quant(qd, kc, vc, ks, vs, lens, layer)
        r = decode_attention_quant(qd, kc, vc, ks, vs, lens, layer)
        np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                   rtol=1e-4, atol=1e-4)

    bs, nb, nblocks = 8, 4, 7
    kp, ksp = _quant_cache(k2, (2, nblocks, KV, bs, D))
    vp, vsp = _quant_cache(k3, (2, nblocks, KV, bs, D))
    tbl = jax.random.randint(k1, (B, nb), 0, nblocks)
    lens = jnp.asarray([nb * bs, 13])
    for layer in range(2):
        op = ops.paged_decode_attention_quant(qd, kp, vp, ksp, vsp, tbl,
                                              lens, layer)
        rp = decode_attention_paged_quant(qd, kp, vp, ksp, vsp, tbl, lens,
                                          layer)
        np.testing.assert_allclose(np.asarray(op), np.asarray(rp),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("R,D,rb", [(512, 64, 128), (96, 256, 32),
                                    (64, 1024, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_kernel(R, D, rb, dtype):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (R, D), dtype)
    w = jax.random.normal(k2, (D,), jnp.float32)
    o = rmsnorm_fwd(x, w, row_block=rb, interpret=True)
    r = ref.rmsnorm_ref(x, w)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("b,nc,q,h,p,n", [
    (2, 3, 16, 4, 8, 16),
    (1, 2, 32, 2, 16, 8),
    (1, 4, 64, 8, 32, 32),
])
def test_ssd_intra_kernel(b, nc, q, h, p, n):
    ks = jax.random.split(KEY, 5)
    X = jax.random.normal(ks[0], (b, nc, q, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, nc, q, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, nc, q, n)) * 0.5
    C = jax.random.normal(ks[4], (b, nc, q, n)) * 0.5
    y, s, acs = ssd_intra_fwd(X, dt, A, B, C, interpret=True)
    yr, sr, _, acsr = ref.ssd_intra_ref(X, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(acs), np.asarray(acsr),
                               rtol=1e-5, atol=1e-5)


def test_ops_ssd_full_matches_jnp_path():
    """ops.ssd_scan (kernel intra + jnp inter) == modules.ssd_chunked."""
    from repro.models.modules import ssd_chunked
    ks = jax.random.split(KEY, 5)
    b, l, h, p, n, chunk = 2, 48, 4, 8, 16, 16
    X = jax.random.normal(ks[0], (b, l, h, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    B = jax.random.normal(ks[3], (b, l, n)) * 0.5
    C = jax.random.normal(ks[4], (b, l, n)) * 0.5
    y1, f1 = ops.ssd_scan(X, dt, A, B, C, chunk)
    y2, f2 = ssd_chunked(X, dt, A, B, C, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f2),
                               rtol=1e-4, atol=1e-4)


def _flash_grouped(q, k, v, causal, win, qb, kb):
    """``ops.flash_attention`` (the entry the model trains through) on
    grouped operands: q (B, KV, G, Lq, D), k/v (B, KV, Lk, D), moved to
    the model layout and back."""
    B, KV, G, Lq, D = q.shape
    qm = jnp.moveaxis(q, 3, 1).reshape(B, Lq, KV * G, D)
    o = ops.flash_attention(qm, jnp.moveaxis(k, 2, 1), jnp.moveaxis(v, 2, 1),
                            causal=causal, window=win, q_block=qb,
                            k_block=kb)
    return jnp.moveaxis(o.reshape(B, Lq, KV, G, D), 1, 3)


@pytest.mark.parametrize("B,KV,G,Lq,Lk,D,causal,win,qb,kb", [
    (2, 2, 2, 64, 64, 32, True, None, 128, 32),
    (1, 1, 4, 128, 128, 64, True, 48, 128, 32),
    (1, 2, 2, 64, 64, 32, False, None, 128, 16),
    # lengths no legal tile divides: queries padded to 384 (3 tiles of
    # 128), keys to 304 (tiles of 16); padded keys must get no weight
    (1, 1, 2, 300, 300, 32, True, None, 128, 64),
    (1, 2, 2, 300, 300, 16, False, None, 128, 64),
])
def test_flash_bwd_kernel_matches_autodiff(B, KV, G, Lq, Lk, D, causal,
                                           win, qb, kb):
    """Pallas fwd+bwd kernels through custom_vjp == autodiff of the naive
    reference (GQA grads sum over the query-head group)."""
    def naive_loss(q, k, v):
        o = ref.flash_attention_ref(q, k, v, causal=causal, window=win)
        return (o.astype(jnp.float32) ** 2).sum()

    def kernel_loss(q, k, v):
        o = _flash_grouped(q, k, v, causal, win, qb, kb)
        return (o.astype(jnp.float32) ** 2).sum()

    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (B, KV, G, Lq, D), jnp.float32)
    k = jax.random.normal(k2, (B, KV, Lk, D), jnp.float32)
    v = jax.random.normal(k3, (B, KV, Lk, D), jnp.float32)
    gk = jax.grad(kernel_loss, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(naive_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("B,KV,G,Lq,Lk,D,causal,win,qb,kb", [
    # rectangular causal (Lq < Lk): queries are the LAST Lq of Lk
    # positions — the suffix-prefill shape the prefix cache dispatches
    (1, 2, 4, 32, 96, 32, True, None, 128, 32),
    (2, 1, 2, 16, 80, 16, True, None, 128, 16),
    # ragged masks: sliding window on top of the causal offset
    (2, 2, 2, 48, 96, 16, True, 32, 128, 32),
    (1, 3, 2, 96, 96, 32, True, 48, 128, 32),
    # GQA with a key length the preferred tile does not divide
    (1, 1, 8, 32, 96, 64, True, None, 128, 64),
    # rectangular, windowed, and both lengths padded (200 -> 256 queries,
    # 300 -> 304 keys)
    (1, 2, 2, 200, 300, 16, True, 64, 128, 32),
])
def test_flash_bwd_kernel_gqa_ragged_grad_check(B, KV, G, Lq, Lk, D,
                                                causal, win, qb, kb):
    """Gradient check for kernels/flash_attention_bwd.py on GQA and
    ragged-mask (rectangular-causal / windowed) shapes: the Pallas
    fwd+bwd pair through custom_vjp must match autodiff of the jnp
    reference for dq, dk and dv — including the masked-out regions
    (grads there must be exactly zero, not garbage) and the GQA
    sum-over-group reduction into dk/dv."""
    k1, k2, k3, k4 = jax.random.split(KEY, 4)
    q = jax.random.normal(k1, (B, KV, G, Lq, D), jnp.float32)
    k = jax.random.normal(k2, (B, KV, Lk, D), jnp.float32)
    v = jax.random.normal(k3, (B, KV, Lk, D), jnp.float32)
    # non-uniform cotangent so dv is not a plain row sum
    cot = jax.random.normal(k4, (B, KV, G, Lq, D), jnp.float32)

    def kernel_loss(q, k, v):
        o = _flash_grouped(q, k, v, causal, win, qb, kb)
        return (o.astype(jnp.float32) * cot).sum()

    def naive_loss(q, k, v):
        o = ref.flash_attention_ref(q, k, v, causal=causal, window=win)
        return (o.astype(jnp.float32) * cot).sum()

    gk = jax.grad(kernel_loss, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(naive_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(gk, gn, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3, err_msg=name)
    # keys a sliding window makes unreachable (kpos <= qpos - window for
    # every query; max qpos is Lk - 1) must carry exactly zero gradient
    if win is not None and Lk - Lq >= win:
        dead = Lk - Lq - win + 1                 # first query sees >= this
        np.testing.assert_array_equal(np.asarray(gn[1][:, :, :dead]), 0.0)
        np.testing.assert_array_equal(np.asarray(gk[1][:, :, :dead]), 0.0)
        np.testing.assert_array_equal(np.asarray(gk[2][:, :, :dead]), 0.0)


def test_ops_layout_adapters():
    """ops.flash_attention / decode_attention accept model-layout tensors."""
    k1, k2, k3 = jax.random.split(KEY, 3)
    B, L, H, KV, D = 2, 32, 4, 2, 16
    q = jax.random.normal(k1, (B, L, H, D))
    k = jax.random.normal(k2, (B, L, KV, D))
    v = jax.random.normal(k3, (B, L, KV, D))
    o = ops.flash_attention(q, k, v, causal=True)
    from repro.models.modules import flash_attention as jnp_fa
    r = jnp_fa(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=1e-4,
                               atol=1e-4)

    # decode reads the stacked KV-head-major arena (n, B, KV, S, D)
    qd = jax.random.normal(k1, (B, H, D))
    lens = jnp.asarray([L, L // 2])
    ka = jnp.stack([v, k]).swapaxes(2, 3)
    va = jnp.stack([k, v]).swapaxes(2, 3)
    from repro.models.modules import decode_attention as jnp_dec
    for layer in range(2):
        od = ops.decode_attention(qd, ka, va, lens, layer)
        rd = jnp_dec(qd, ka, va, lens, layer)
        np.testing.assert_allclose(np.asarray(od), np.asarray(rd),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("total,preferred,align,want", [
    (96, 256, 128, (96, 96)),        # fits: the whole axis
    (512, 256, 128, (256, 512)),     # largest aligned divisor
    (300, 128, 128, (128, 384)),     # no divisor: padded up to align
    (300, 64, 8, (16, 304)),
    (300, 32, 128, (128, 384)),      # preferred below align is raised
    (64, 32, 128, (64, 64)),
])
def test_tile_picks_legal_blocks(total, preferred, align, want):
    blk, padded = ops._tile(total, preferred, align)
    assert (blk, padded) == want
    assert padded % blk == 0 and padded >= total
    assert blk == padded or blk % align == 0


# ------------------------------------------------------------------ #
# grouped matmul of the held experts, and flash with a softmax scale
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("sizes", [(5, 0, 40, 3), (0, 0, 0, 0), (96, 0, 0, 0)])
def test_grouped_matmul_kernel_matches_ragged_dot(sizes):
    """The megablox kernel (interpret mode) computes each group's rows as
    ``jax.lax.ragged_dot`` does, empty groups and rows past the groups
    included (those rows: no defined value, so not compared); so do its
    gradients on the grouped rows."""
    M, K, N = 96, 128, 256
    kx, kw = jax.random.split(KEY)
    x = jax.random.normal(kx, (M, K), jnp.float32)
    w = jax.random.normal(kw, (4, K, N), jnp.float32) / np.sqrt(K)
    gs = jnp.asarray(sizes, jnp.int32)
    n = sum(sizes)
    got = ops.grouped_matmul(x, w, gs)
    want = jax.lax.ragged_dot(x, w, gs)
    np.testing.assert_allclose(np.asarray(got[:n]), np.asarray(want[:n]),
                               rtol=1e-5, atol=1e-5)
    mask = (jnp.arange(M) < n)[:, None]

    def loss(f):
        return lambda x, w: jnp.sum(jnp.where(mask, f(x, w, gs), 0.0) ** 2)

    gk = jax.grad(loss(ops.grouped_matmul), argnums=(0, 1))(x, w)
    gr = jax.grad(loss(jax.lax.ragged_dot), argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gk[0][:n]), np.asarray(gr[0][:n]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(gk[1]), np.asarray(gr[1]),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("use_pallas", [True, False])
@pytest.mark.parametrize("layer", [0, 2])
def test_grouped_matmul_reads_a_layer_of_the_stack(use_pallas, layer):
    """A layer's grouped matmul handed the whole (layers, groups, K, N)
    stack and the layer's index equals the one handed that layer's
    slice, for the megablox kernel (interpret mode) and the ragged dot."""
    from repro.models import modules as M
    M_, K, N, U = 96, 128, 256, 3
    kx, kw = jax.random.split(KEY)
    x = jax.random.normal(kx, (M_, K), jnp.float32)
    w = jax.random.normal(kw, (U, 4, K, N), jnp.float32) / np.sqrt(K)
    gs = jnp.asarray((5, 0, 40, 3), jnp.int32)
    got = jax.jit(lambda x, w, li: M.grouped_matmul(
        x, w, gs, use_pallas, layer=li))(x, w, jnp.int32(layer))
    want = M.grouped_matmul(x, w[layer], gs, use_pallas)
    np.testing.assert_allclose(np.asarray(got[:48]), np.asarray(want[:48]),
                               rtol=1e-6, atol=1e-6)


def test_flash_softmax_scale_matches_jnp():
    """``sm_scale`` reaches the Pallas flash forward and backward as it
    reaches the jnp flash: MLA's YaRN scale on 192-wide heads."""
    from repro.models.modules import flash_attention as jnp_flash
    B, L, H, D = 1, 128, 2, 192
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(kk, (B, L, H, D), jnp.float32) for kk in ks)
    s = 0.11472

    def f(fn, **kw):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=True, **kw) ** 2)

    o = ops.flash_attention(q, k, v, sm_scale=s)
    o_ref = jnp_flash(q, k, v, causal=True, scale=s)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=2e-4,
                               atol=2e-4)
    g = jax.grad(f(ops.flash_attention, sm_scale=s), argnums=(0, 1, 2))(
        q, k, v)
    g_ref = jax.grad(f(jnp_flash, scale=s), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3,
                                   atol=2e-3)
