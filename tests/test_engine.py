"""Serving engine behaviour: token-identity of the chunked early-exit
decode vs the reference fixed scan (sampling AND greedy), early-exit
correctness when every sequence finishes, continuous-batching greedy
equivalence per sequence, and slot-refill bookkeeping under ragged prompt
lengths with more requests than slots."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hybrid_engine import HybridEngine
from repro.core.ppo import PPOConfig, PPOTrainer
from repro.launch.mesh import make_local_mesh
from repro.models.config import ModelConfig
from repro.models import reward as R
from repro.models import transformer as T
from repro.serving.engine import GenerationEngine, Request
from repro.serving.generate import generate

V = 64
CFG = ModelConfig(name="eng", arch_type="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=V,
                  compute_dtype="float32", remat=False)
KEY = jax.random.PRNGKey(0)
PARAMS = T.init_params(CFG, KEY)


def ref_generate(tokens, max_new, *, temperature=0.0, eos_id=None, key=KEY):
    return generate(CFG, PARAMS, tokens, key, max_new_tokens=max_new,
                    temperature=temperature, eos_id=eos_id)


# ------------------------------------------------------------------ #
# fixed-batch path
# ------------------------------------------------------------------ #
def test_fixed_path_token_identical_sampling():
    """Chunked decode preserves the PRNG-split sequence: stochastic
    sampling is bit-identical to the single-scan reference, across uneven
    chunk boundaries.  (An eos_id is set so the engine actually chunks —
    without one it fuses into a single dispatch.)"""
    prompts = jax.random.randint(jax.random.PRNGKey(1), (4, 6), 0, V)
    key = jax.random.PRNGKey(2)
    ref = ref_generate(prompts, 8, temperature=1.0, eos_id=V - 1, key=key)
    eng = GenerationEngine(CFG, max_new_tokens=8, temperature=1.0,
                           eos_id=V - 1, chunk=3)
    out = eng.generate(PARAMS, prompts, key)
    np.testing.assert_array_equal(np.asarray(ref["sequences"]),
                                  np.asarray(out["sequences"]))
    np.testing.assert_array_equal(np.asarray(ref["response_mask"]),
                                  np.asarray(out["response_mask"]))
    assert eng.last_stats["decode_steps"] <= 8


def test_no_eos_single_fused_dispatch():
    """eos_id=None cannot early-exit, so the engine must not pay per-chunk
    host syncs: one fused dispatch regardless of the chunk setting."""
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 4), 0, V)
    eng = GenerationEngine(CFG, max_new_tokens=9, temperature=1.0, chunk=2)
    out = eng.generate(PARAMS, prompts, jax.random.PRNGKey(2))
    assert eng.last_stats["decode_steps"] == 9
    assert list(eng._chunk_fns) == [9]       # compiled once, full length
    ref = ref_generate(prompts, 9, temperature=1.0,
                       key=jax.random.PRNGKey(2))
    np.testing.assert_array_equal(np.asarray(ref["sequences"]),
                                  np.asarray(out["sequences"]))


def test_early_exit_when_all_finish():
    """All rows share a prompt, so greedy decode finishes them at the
    same step; the engine must stop dispatching chunks early and still
    return sequences identical to the full fixed scan (which includes
    the forced-EOS padding and mask-False tail)."""
    prompts = jnp.tile(
        jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0, V), (4, 1))
    probe = ref_generate(prompts, 16)
    eos = int(probe["sequences"][0, 6 + 2])      # token emitted at step 2
    ref = ref_generate(prompts, 16, eos_id=eos)
    eng = GenerationEngine(CFG, max_new_tokens=16, temperature=0.0,
                           eos_id=eos, chunk=4)
    out = eng.generate(PARAMS, prompts, KEY)
    np.testing.assert_array_equal(np.asarray(ref["sequences"]),
                                  np.asarray(out["sequences"]))
    np.testing.assert_array_equal(np.asarray(ref["response_mask"]),
                                  np.asarray(out["response_mask"]))
    assert eng.last_stats["decode_steps"] < 16   # early exit actually fired
    # mask includes the EOS emission itself, nothing after it
    row = np.asarray(out["response_mask"][0])
    n = int(row[6:].sum())
    assert 1 <= n <= 3                            # finished at/before step 2
    assert int(out["sequences"][0, 6 + n - 1]) == eos
    assert not row[6 + n:].any()


def test_response_mask_no_eos_covers_response():
    """eos_id=None: nothing finishes, mask is True on the whole response
    region and False on the prompt."""
    prompts = jax.random.randint(jax.random.PRNGKey(4), (2, 5), 0, V)
    eng = GenerationEngine(CFG, max_new_tokens=6, temperature=1.0)
    out = eng.generate(PARAMS, prompts, KEY)
    mask = np.asarray(out["response_mask"])
    assert mask[:, :5].sum() == 0
    assert mask[:, 5:].all()


# ------------------------------------------------------------------ #
# continuous batching
# ------------------------------------------------------------------ #
def _ragged_requests(lengths, budgets):
    rng = np.random.default_rng(7)
    return [Request(uid=i,
                    tokens=rng.integers(0, V, size=lp).astype(np.int32),
                    max_new_tokens=mn)
            for i, (lp, mn) in enumerate(zip(lengths, budgets))]


def test_continuous_greedy_matches_fixed_per_sequence():
    """Greedy continuous-batching output is token-identical to running
    each request alone through the reference fixed path — slot packing,
    shape-bucketed ragged prefill, and refills must not leak between
    sequences."""
    reqs = _ragged_requests([3, 7, 5, 4, 6, 3], [5, 8, 4, 6, 3, 7])
    eng = GenerationEngine(CFG, max_new_tokens=8, temperature=0.0, chunk=4)
    outs = eng.serve(PARAMS, reqs, jax.random.PRNGKey(9), slots=3)
    assert sorted(c.uid for c in outs) == list(range(6))
    for c in outs:
        r = reqs[c.uid]
        assert c.tokens.size == r.max_new_tokens
        ref = ref_generate(jnp.asarray(r.tokens)[None], r.max_new_tokens)
        np.testing.assert_array_equal(
            c.tokens, np.asarray(ref["sequences"][0, len(r.tokens):]))


def test_continuous_eos_stops_per_slot():
    """A slot whose sequence hits EOS frees early; its completion ends at
    the EOS token and matches the per-sequence reference."""
    reqs = _ragged_requests([4, 6, 5], [16, 16, 16])
    # find a real greedy token to use as EOS for request 0
    probe = ref_generate(jnp.asarray(reqs[0].tokens)[None], 16)
    eos = int(probe["sequences"][0, 4 + 1])      # its 2nd generated token
    eng = GenerationEngine(CFG, max_new_tokens=16, temperature=0.0,
                           eos_id=eos, chunk=4)
    outs = {c.uid: c for c in eng.serve(PARAMS, reqs,
                                        jax.random.PRNGKey(0), slots=2)}
    for uid, c in outs.items():
        r = reqs[uid]
        ref = ref_generate(jnp.asarray(r.tokens)[None], 16, eos_id=eos)
        n = int(np.asarray(ref["response_mask"][0]).sum())
        assert c.tokens.size == n
        np.testing.assert_array_equal(
            c.tokens,
            np.asarray(ref["sequences"][0, len(r.tokens):len(r.tokens) + n]))
    assert outs[0].finish_reason == "eos"
    assert int(outs[0].tokens[-1]) == eos
    assert all(outs[u].finish_reason in ("eos", "length") for u in outs)


def test_slot_refill_bookkeeping():
    """More requests than slots: every request completes exactly once,
    within its budget, and the scheduler reports full admission."""
    lengths = [3, 9, 4, 7, 5, 6, 8, 3, 4]
    budgets = [2, 5, 7, 3, 6, 4, 2, 5, 3]
    reqs = _ragged_requests(lengths, budgets)
    eng = GenerationEngine(CFG, max_new_tokens=8, temperature=0.0, chunk=2)
    outs = eng.serve(PARAMS, reqs, jax.random.PRNGKey(5), slots=2)
    assert sorted(c.uid for c in outs) == list(range(len(reqs)))
    for c in outs:
        assert c.tokens.size == reqs[c.uid].max_new_tokens
    st = eng.last_stats
    assert st["admitted"] == len(reqs)
    assert st["generated_tokens"] == sum(budgets)
    # arena was 2 wide: at least ceil(total/2 / chunk) chunks ran
    assert st["requests"] == len(reqs)


def test_serve_rejects_too_long_request():
    reqs = _ragged_requests([6], [8])
    eng = GenerationEngine(CFG, max_new_tokens=8, temperature=0.0)
    with pytest.raises(ValueError):
        eng.serve(PARAMS, reqs, KEY, slots=1, max_seq_len=10)


def test_zero_budget_requests():
    """max_new_tokens=0: fixed path returns the prompts untouched;
    continuous path completes the request with no tokens and no slot."""
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 5), 0, V)
    eng = GenerationEngine(CFG, max_new_tokens=0, temperature=0.0)
    out = eng.generate(PARAMS, prompts, KEY)
    np.testing.assert_array_equal(np.asarray(out["sequences"]),
                                  np.asarray(prompts))
    assert not np.asarray(out["response_mask"]).any()

    reqs = _ragged_requests([4, 6], [0, 3])
    eng2 = GenerationEngine(CFG, max_new_tokens=8, temperature=0.0, chunk=2)
    outs = {c.uid: c for c in eng2.serve(PARAMS, reqs,
                                         jax.random.PRNGKey(3), slots=1)}
    assert outs[0].tokens.size == 0
    assert outs[0].finish_reason == "length"
    assert outs[1].tokens.size == 3


# ------------------------------------------------------------------ #
# integration: Hybrid Engine + PPO trainer use the engine path
# ------------------------------------------------------------------ #
def test_hybrid_engine_factory_and_ppo_metrics():
    mesh = make_local_mesh()
    he = HybridEngine(CFG, mesh)
    eng = he.generation_engine(max_new_tokens=4)
    prompts = jax.random.randint(jax.random.PRNGKey(6), (2, 5), 0, V)
    out = eng.generate(he.to_inference(PARAMS), prompts, KEY)
    assert out["sequences"].shape == (2, 9)

    trainer = PPOTrainer(
        actor_cfg=CFG, critic_cfg=CFG,
        actor_params=PARAMS, critic_params=R.init_params(CFG, KEY),
        ref_params=PARAMS, reward_params=R.init_params(CFG, KEY),
        ppo=PPOConfig(max_new_tokens=4, use_ema=False), engine=he)
    exp, gm = trainer.generate_experience(prompts, jax.random.PRNGKey(8))
    assert exp.sequences.shape == (2, 9)
    for k in ("gen_tok_s", "decode_steps", "gen_len", "reward_score"):
        assert k in gm
    assert gm["decode_steps"] == 4.0


@pytest.mark.parametrize("size,partitioned", [(1, False), (4, True)])
def test_engine_marks_multi_device_config_partitioned(size, partitioned):
    """A generation engine on a multi-device mesh marks its config
    partitioned, so ModelConfig.pallas_kernels keeps it on the jnp code;
    a one-device mesh leaves the config as given."""
    from types import SimpleNamespace
    eng = GenerationEngine(CFG, max_new_tokens=4,
                           mesh=SimpleNamespace(size=size))
    assert eng.cfg.partitioned is partitioned
    assert eng.cfg.replace(partitioned=False) == CFG
