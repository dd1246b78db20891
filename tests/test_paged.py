"""Paged KV-cache serving: block-allocator invariants (exhaustion ->
backpressure without deadlock, reuse after harvest, fragmentation bound
over 1k ragged cycles), paged-vs-dense token identity (greedy and seeded
sampling), preemption correctness, and the paged Pallas kernel vs its
gather reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_attention import paged_decode_attention_fwd
from repro.models.config import ModelConfig
from repro.models import transformer as T
from repro.serving.block_pool import TRASH_BLOCK, BlockAllocator, blocks_for
from repro.serving.engine import GenerationEngine, Request
from repro.serving.generate import generate

V = 64
CFG = ModelConfig(name="paged", arch_type="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=V,
                  compute_dtype="float32", remat=False)
KEY = jax.random.PRNGKey(0)
PARAMS = T.init_params(CFG, KEY)


def _ragged_requests(lengths, budgets, seed=7):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    tokens=rng.integers(0, V, size=lp).astype(np.int32),
                    max_new_tokens=mn)
            for i, (lp, mn) in enumerate(zip(lengths, budgets))]


def _engine(layout, bs=4, **kw):
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("chunk", 4)
    return GenerationEngine(CFG, kv_layout=layout, block_size=bs, **kw)


# ------------------------------------------------------------------ #
# BlockAllocator invariants
# ------------------------------------------------------------------ #
def test_allocator_basic_accounting():
    a = BlockAllocator(9, 4, watermark=2)           # 8 usable blocks
    assert a.capacity == 8 and a.num_free == 8
    assert a.blocks_for(0) == 0 and a.blocks_for(1) == 1
    assert a.blocks_for(4) == 1 and a.blocks_for(5) == 2
    ids = a.alloc(3)
    assert len(ids) == 3 and TRASH_BLOCK not in ids
    assert a.num_free == 5 and a.num_used == 3 and a.high_water == 3
    # watermark: 5 free, reserve 2 -> at most 3 more admissible tokens' blocks
    assert a.can_admit(3 * 4) and not a.can_admit(3 * 4 + 1)
    assert a.can_admit(5 * 4, ignore_watermark=True)
    a.free(ids)
    assert a.num_free == 8 and a.high_water == 3


def test_allocator_exhaustion_and_errors():
    a = BlockAllocator(4, 2)                        # 3 usable
    ids = a.alloc(3)
    assert a.alloc(1) is None and a.num_free == 0   # exhausted, no change
    a.free(ids[:1])
    assert a.alloc(1) is not None
    with pytest.raises(ValueError):
        a.free([TRASH_BLOCK])
    with pytest.raises(ValueError):
        a.free([ids[1], ids[1]])                    # double free


def test_allocator_fragmentation_bound_1k_ragged_cycles():
    """Fixed-size blocks cannot fragment externally: after 1k ragged
    alloc/free cycles an allocation succeeds iff enough blocks are free,
    and releasing everything restores full capacity (no leaks)."""
    rng = np.random.default_rng(0)
    a = BlockAllocator(65, 8)                       # 64 usable
    live = []
    for _ in range(1000):
        if live and (rng.random() < 0.5 or a.num_free == 0):
            a.free(live.pop(rng.integers(len(live))))
        else:
            n = int(rng.integers(1, 9))
            got = a.alloc(n)
            assert (got is not None) == (n <= 64 - sum(map(len, live)))
            if got is not None:
                live.append(got)
        held = sum(map(len, live))
        assert a.num_free == 64 - held              # exact, every cycle
        assert a.alloc(a.num_free + 1) is None
    for ids in live:
        a.free(ids)
    assert a.num_free == a.capacity == 64
    assert a.high_water <= 64


# ------------------------------------------------------------------ #
# paged Pallas kernel vs gather reference
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("B,KV,G,D,bs,nb,nblocks", [
    (2, 2, 2, 32, 8, 4, 12),
    (1, 1, 8, 64, 16, 2, 5),
    (3, 4, 1, 16, 8, 8, 40),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_ref(B, KV, G, D, bs, nb, nblocks, dtype):
    k1, k2, k3, k4, k5 = jax.random.split(KEY, 5)
    q = jax.random.normal(k1, (B, KV, G, D), dtype)
    kp = jax.random.normal(k2, (nblocks, KV, bs, D), dtype)
    vp = jax.random.normal(k3, (nblocks, KV, bs, D), dtype)
    tbl = jax.random.randint(k4, (B, nb), 0, nblocks)
    lens = jax.random.randint(k5, (B,), 1, nb * bs + 1)
    # the pool as the second layer of a stacked pool, read where it lies
    stack = lambda x: jnp.stack([jnp.zeros_like(x), x])
    o = paged_decode_attention_fwd(q, stack(kp), stack(vp), tbl, lens,
                                   jnp.int32(1), interpret=True)
    r = ref.paged_decode_attention_ref(q, kp, vp, tbl, lens)
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=tol, atol=tol)


# ------------------------------------------------------------------ #
# paged-vs-dense token identity
# ------------------------------------------------------------------ #
def test_paged_matches_dense_greedy():
    reqs = _ragged_requests([3, 7, 5, 4, 6, 3], [5, 8, 4, 6, 3, 7])
    kw = dict(slots=3, max_seq_len=16)              # 16 % block_size == 0
    d = {c.uid: c for c in _engine("dense").serve(
        PARAMS, reqs, jax.random.PRNGKey(9), **kw)}
    p = {c.uid: c for c in _engine("paged").serve(
        PARAMS, reqs, jax.random.PRNGKey(9), **kw)}
    assert sorted(p) == sorted(d) == list(range(6))
    for uid in d:
        np.testing.assert_array_equal(d[uid].tokens, p[uid].tokens)


def test_paged_matches_dense_seeded_sampling():
    """Stochastic sampling: same admission order => same PRNG-split
    sequence => bit-identical streams across KV layouts."""
    reqs = _ragged_requests([4, 6, 3, 5, 7], [6, 8, 5, 7, 4])
    mk = lambda layout: _engine(layout, temperature=1.0, top_k=8,
                                eos_id=V - 1)
    kw = dict(slots=2, max_seq_len=16)
    d = {c.uid: c for c in mk("dense").serve(
        PARAMS, reqs, jax.random.PRNGKey(3), **kw)}
    p = {c.uid: c for c in mk("paged").serve(
        PARAMS, reqs, jax.random.PRNGKey(3), **kw)}
    for uid in d:
        np.testing.assert_array_equal(d[uid].tokens, p[uid].tokens)
        assert d[uid].finish_reason == p[uid].finish_reason


def test_paged_block_reuse_after_harvest_keeps_streams_identical():
    """A pool barely larger than one request forces every admission to
    reuse just-freed blocks; streams must still match the per-request
    reference (stale KV fully dead)."""
    reqs = _ragged_requests([3, 9, 4, 7, 5, 6], [8, 5, 7, 3, 6, 4])
    eng = _engine("paged")
    outs = eng.serve(PARAMS, reqs, jax.random.PRNGKey(5), slots=2,
                     max_seq_len=20, num_blocks=11, watermark=0)
    assert sorted(c.uid for c in outs) == list(range(6))
    assert eng.last_stats["block_high_water"] <= 10
    for c in outs:
        r = reqs[c.uid]
        ref_out = generate(CFG, PARAMS, jnp.asarray(r.tokens)[None], KEY,
                           max_new_tokens=r.max_new_tokens, temperature=0.0)
        np.testing.assert_array_equal(
            c.tokens,
            np.asarray(ref_out["sequences"][0, len(r.tokens):]))


def test_exhaustion_backpressure_no_deadlock():
    """Pool admits ~1 request at a time: admission must wait for blocks
    (backpressure), possibly preempt, and still complete every request
    with correct greedy tokens — the scheduler cannot wedge."""
    lengths = [3, 9, 4, 7, 5, 6, 8, 3, 4]
    budgets = [2, 5, 7, 3, 6, 4, 2, 5, 3]
    reqs = _ragged_requests(lengths, budgets)
    eng = _engine("paged", chunk=2)
    outs = eng.serve(PARAMS, reqs, jax.random.PRNGKey(5), slots=3,
                     max_seq_len=20, num_blocks=6, watermark=0)
    assert sorted(c.uid for c in outs) == list(range(len(reqs)))
    st = eng.last_stats
    assert st["max_concurrency"] <= 2               # pool-bound, not slots
    assert st["block_high_water"] <= 5
    for c in outs:
        r = reqs[c.uid]
        assert c.tokens.size == r.max_new_tokens
        ref_out = generate(CFG, PARAMS, jnp.asarray(r.tokens)[None], KEY,
                           max_new_tokens=r.max_new_tokens, temperature=0.0)
        np.testing.assert_array_equal(
            c.tokens,
            np.asarray(ref_out["sequences"][0, len(r.tokens):]))


def test_watermark_reserves_headroom():
    """With a watermark covering each admitted sequence's future appends,
    admission keeps enough blocks free for decode-time growth and the
    same tight pool finishes without any preemption."""
    reqs = _ragged_requests([6, 6, 6, 6], [8, 8, 8, 8])
    eng = _engine("paged", chunk=2)
    eng.serve(PARAMS, reqs, jax.random.PRNGKey(1), slots=4,
              max_seq_len=16, num_blocks=9, watermark=4)
    assert eng.last_stats["preemptions"] == 0
    # and the watermark visibly limited concurrent admissions
    assert eng.last_stats["max_concurrency"] <= 2

    # the same pool with the watermark disabled over-admits and must
    # preempt to make progress — yet still completes every request
    eng0 = _engine("paged", chunk=2)
    outs = eng0.serve(PARAMS, reqs, jax.random.PRNGKey(1), slots=4,
                      max_seq_len=16, num_blocks=9, watermark=0)
    assert sorted(c.uid for c in outs) == list(range(4))
    assert eng0.last_stats["preemptions"] > 0


def test_zero_budget_and_too_long_requests_paged():
    reqs = _ragged_requests([4, 6], [0, 3])
    eng = _engine("paged")
    outs = {c.uid: c for c in eng.serve(PARAMS, reqs,
                                        jax.random.PRNGKey(3), slots=1)}
    assert outs[0].tokens.size == 0 and outs[1].tokens.size == 3
    with pytest.raises(ValueError):                 # exceeds pool capacity
        eng.serve(PARAMS, _ragged_requests([8], [8]),
                  jax.random.PRNGKey(0), slots=1, num_blocks=3)
    with pytest.raises(ValueError):                 # exceeds max_seq_len
        eng.serve(PARAMS, _ragged_requests([8], [8]),
                  jax.random.PRNGKey(0), slots=1, max_seq_len=10)


def test_paged_rejects_unsupported_configs():
    ssm_cfg = ModelConfig(name="s", arch_type="ssm", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=V,
                          ssm_state=16, compute_dtype="float32", remat=False)
    with pytest.raises(NotImplementedError):
        GenerationEngine(ssm_cfg, max_new_tokens=4, kv_layout="paged")
    # int8-KV is paged-capable now; MLA (latent cache geometry) and VLM
    # remain dense-only
    mla_cfg = ModelConfig(name="m", arch_type="dense", mla=True,
                          kv_lora_rank=32, qk_nope_head_dim=16,
                          qk_rope_head_dim=8, v_head_dim=16, n_layers=2,
                          d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
                          vocab_size=V, compute_dtype="float32", remat=False)
    with pytest.raises(NotImplementedError):
        GenerationEngine(mla_cfg, max_new_tokens=4, kv_layout="paged")
    with pytest.raises(NotImplementedError):
        GenerationEngine(mla_cfg.replace(kv_quant=True), max_new_tokens=4,
                         kv_layout="paged")
    with pytest.raises(NotImplementedError):
        GenerationEngine(CFG.replace(sliding_window=8), max_new_tokens=4,
                         kv_layout="paged")
    with pytest.raises(ValueError):
        GenerationEngine(CFG, max_new_tokens=4, kv_layout="banana")
    # pool knobs are paged-only
    with pytest.raises(ValueError):
        _engine("dense").serve(PARAMS, _ragged_requests([4], [2]),
                               jax.random.PRNGKey(0), slots=1, num_blocks=8)


# ------------------------------------------------------------------ #
# int8 KV over the paged path: the pool stores int8 K/V + per-row fp32
# scale planes that travel with their blocks (see docs/serving.md)
# ------------------------------------------------------------------ #
QCFG = CFG.replace(kv_quant=True)


def _qengine(cfg, **kw):
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("temperature", 0.0)
    kw.setdefault("chunk", 4)
    return GenerationEngine(cfg, kv_layout="paged", block_size=4, **kw)


def test_paged_int8_pool_has_scale_planes():
    pool = T.init_paged_cache(QCFG, 6, 4)
    leaf = pool[0][0]
    assert leaf["k"].dtype == jnp.int8 and leaf["v"].dtype == jnp.int8
    assert leaf["k_scale"].shape == (QCFG.n_layers, 6, QCFG.n_kv_heads, 4)
    assert leaf["k_scale"].dtype == jnp.float32


def test_paged_int8_matches_dense_int8_bitwise():
    """The identity suite: two KV layouts over the SAME quantization must
    stream bit-identical greedy tokens (same quantized rows, same score
    algebra, virtual-dense gather == arena)."""
    reqs = _ragged_requests([3, 7, 5, 4, 6, 3], [5, 8, 4, 6, 3, 7])
    kw = dict(slots=3, max_seq_len=16)
    d = {c.uid: c for c in GenerationEngine(
        QCFG, kv_layout="dense", max_new_tokens=8, temperature=0.0,
        chunk=4).serve(PARAMS, reqs, jax.random.PRNGKey(9), **kw)}
    p = {c.uid: c for c in _qengine(QCFG).serve(
        PARAMS, reqs, jax.random.PRNGKey(9), **kw)}
    assert sorted(p) == sorted(d) == list(range(6))
    for uid in d:
        np.testing.assert_array_equal(d[uid].tokens, p[uid].tokens)


def test_paged_int8_greedy_argmax_parity_vs_fp():
    """int8 on/off over the paged path: quantization shifts logits
    within the asserted error budget (see test_models'
    test_kv_quant_decode_parity), so greedy argmax — what generation
    consumes — must match the fp path on a margin-healthy suite.  On
    these requests every fp greedy step has a top-2 logit margin of at
    least 0.08, above the largest int8 logit shift seen on them (0.05);
    a suite whose margin falls below the int8 shift flips argmax
    without any fault in the int8 path."""
    reqs = _ragged_requests([3, 7, 5, 4, 6, 3], [5, 8, 4, 6, 3, 7], seed=7)
    kw = dict(slots=3, max_seq_len=16)
    f = {c.uid: c for c in _qengine(CFG).serve(
        PARAMS, reqs, jax.random.PRNGKey(9), **kw)}
    q = {c.uid: c for c in _qengine(QCFG).serve(
        PARAMS, reqs, jax.random.PRNGKey(9), **kw)}
    assert sorted(q) == sorted(f) == list(range(6))
    for uid in f:
        np.testing.assert_array_equal(f[uid].tokens, q[uid].tokens)


def test_paged_int8_preemption_streams_match_reference():
    """Tight pool forces preemptions; every re-admitted int8 stream must
    still match the per-request int8 fixed-batch reference (quantized
    rows survive the evict/re-prefill cycle)."""
    reqs = _ragged_requests([3, 9, 4, 7, 5, 6], [8, 5, 7, 3, 6, 4])
    eng = _qengine(QCFG, chunk=2)
    outs = eng.serve(PARAMS, reqs, jax.random.PRNGKey(5), slots=3,
                     max_seq_len=20, num_blocks=6, watermark=0)
    assert sorted(c.uid for c in outs) == list(range(6))
    assert eng.last_stats["preemptions"] > 0
    for c in outs:
        r = reqs[c.uid]
        ref_out = generate(QCFG, PARAMS, jnp.asarray(r.tokens)[None], KEY,
                           max_new_tokens=r.max_new_tokens, temperature=0.0)
        np.testing.assert_array_equal(
            c.tokens,
            np.asarray(ref_out["sequences"][0, len(r.tokens):]))


def test_paged_int8_prefix_cache_on_off_within_budget():
    """Prefix-cache admission over an int8 pool: the suffix attends the
    DEQUANTIZED gathered history while a cold prefill attends the
    original fp keys, so streams agree within the quantization budget —
    asserted as greedy argmax parity on this margin-healthy suite —
    and the scale planes must ride the shared blocks (hit rate > 0)."""
    rng = np.random.default_rng(0)
    shared = rng.integers(0, V, size=8).astype(np.int32)
    reqs = [Request(uid=i,
                    tokens=np.concatenate(
                        [shared, rng.integers(0, V, size=4)]).astype(
                            np.int32),
                    max_new_tokens=6)
            for i in range(5)]
    e_on = _qengine(QCFG, prefix_cache=True)
    on = {c.uid: c for c in e_on.serve(PARAMS, reqs, jax.random.PRNGKey(2),
                                       slots=2, max_seq_len=24)}
    off = {c.uid: c for c in _qengine(QCFG).serve(
        PARAMS, reqs, jax.random.PRNGKey(2), slots=2, max_seq_len=24)}
    assert e_on.last_stats["prefill_hit_rate"] > 0.3
    for uid in off:
        np.testing.assert_array_equal(on[uid].tokens, off[uid].tokens)


def test_paged_int8_single_compiled_chunk_graph():
    """Retrace guard: mixed ragged int8 traffic still compiles exactly
    ONE paged chunk graph (admission buckets retrace by design; the
    steady-state decode graph must not)."""
    reqs = _ragged_requests([3, 7, 5, 4, 6, 3], [5, 8, 4, 6, 3, 7])
    eng = _qengine(QCFG)
    eng.serve(PARAMS, reqs, jax.random.PRNGKey(9), slots=3, max_seq_len=16)
    assert eng._paged_chunk_fn._cache_size() == 1
