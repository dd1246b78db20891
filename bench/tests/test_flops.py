"""FLOP and byte functions against hand counts at small sizes."""
from bench.lib import flops as F

M = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "d_ff": 16, "vocab_size": 10}


def test_body_params_hand_count():
    # per layer: wq 8*8, wk 8*4, wv 8*4, wo 8*8, three 8x16 MLP matrices
    assert F.body_params(M) == 2 * (64 + 32 + 32 + 64 + 3 * 128)


def test_causal_pairs():
    assert F.causal_pairs(3, 3) == 6                  # 1 + 2 + 3
    assert F.causal_pairs(2, 5) == 4 + 5              # last two of five
    assert F.causal_pairs(1, 7) == 7                  # one decode query


def test_body_and_head_forward():
    # 3 tokens: 2 FLOPs per weight per token, plus 4*L*H*hd per pair
    assert F.body_fwd(M, 3) == 2 * 3 * 1152 + 4 * 2 * 4 * 2 * 6
    assert F.head_fwd(M, 5) == 2 * 5 * 8 * 10
    assert F.head_fwd(M, 5, 1) == 2 * 5 * 8


def test_ppo_iteration_hand_count():
    B, P, G = 2, 3, 2
    T = P + G
    gen = B * (F.body_fwd(M, T - 1) + F.head_fwd(M, G))
    logp = F.body_fwd(M, T - 1) + F.head_fwd(M, T - 1)
    vals = F.body_fwd(M, T - 1) + F.head_fwd(M, T - 1, 1)
    rew = F.body_fwd(M, T) + F.head_fwd(M, 1, 1)
    r = F.ppo_iteration(M, M, B, P, G)
    assert r["generate"] == gen
    assert r["score"] == B * (2 * logp + vals + rew)
    assert r["train"] == B * 3 * (logp + vals)
    assert r["total"] == gen + r["score"] + r["train"]


def test_kernel_counts():
    # 1 batch, 2 queries over 2 keys (3 causal pairs), 4 heads over 2 KV
    fl, by = F.flash_fwd(1, 2, 2, 4, 2, 8)
    assert fl == 4 * 4 * 8 * 3
    assert by == 2 * 8 * (2 * 2 * 4 + 2 * 2 * 2)
    fl, by = F.flash_bwd(1, 2, 2, 4, 2, 8)
    assert fl == 8 * 4 * 8 * 3
    fl, by = F.decode_attn(10, 4, 2, 8)
    assert (fl, by) == (4 * 4 * 8 * 10, 2 * 2 * 2 * 8 * 10)
    assert F.roofline_seconds(10, 100, 10.0, 10.0) == 10.0
