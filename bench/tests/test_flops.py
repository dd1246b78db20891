"""FLOP and byte functions against hand counts at small sizes; the
layers' FLOPs come from the configuration's reference module."""
import types

from bench.lib import flops as F
from bench.lib import spec as SP

D = SP.load_reference("dense_decoder")
M = {"n_layers": 2, "d_model": 8, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 2, "d_ff": 16, "vocab_size": 10}


def test_body_params_hand_count():
    # per layer: wq 8*8, wk 8*4, wv 8*4, wo 8*8, three 8x16 MLP matrices
    assert D.body_params(M) == 2 * (64 + 32 + 32 + 64 + 3 * 128)


def test_causal_pairs():
    assert F.causal_pairs(3, 3) == 6                  # 1 + 2 + 3
    assert F.causal_pairs(2, 5) == 4 + 5              # last two of five
    assert F.causal_pairs(1, 7) == 7                  # one decode query


def test_body_and_head_forward():
    # 3 tokens: 2 FLOPs per weight per token, plus 4*L*H*hd per pair
    assert D.fwd_flops(M, 3) == 2 * 3 * 1152 + 4 * 2 * 4 * 2 * 6
    # one decode token after 4 cached: 5 pairs
    assert D.fwd_flops(M, 1, 5) == 2 * 1152 + 4 * 2 * 4 * 2 * 5
    assert F.head_fwd(M, 5) == 2 * 5 * 8 * 10
    assert F.head_fwd(M, 5, 1) == 2 * 5 * 8


def test_ppo_iteration_hand_count():
    B, P, G = 2, 3, 2
    T = P + G
    gen = B * (D.fwd_flops(M, T - 1) + F.head_fwd(M, G))
    logp = D.fwd_flops(M, T - 1) + F.head_fwd(M, T - 1)
    vals = D.fwd_flops(M, T - 1) + F.head_fwd(M, T - 1, 1)
    rew = D.fwd_flops(M, T) + F.head_fwd(M, 1, 1)
    r = F.ppo_iteration(D, M, M, B, P, G)
    assert r["generate"] == gen
    assert r["score"] == B * (2 * logp + vals + rew)
    assert r["train"] == B * 3 * (logp + vals)
    assert r["total"] == gen + r["score"] + r["train"]


def test_ppo_iteration_asks_the_reference_for_the_layers():
    # a reference whose layers cost one FLOP a token leaves the heads
    stub = types.SimpleNamespace(fwd_flops=lambda m, n, n_ctx=None: n)
    B, P, G = 2, 3, 2
    T = P + G
    r = F.ppo_iteration(stub, M, M, B, P, G)
    assert r["generate"] == B * ((T - 1) + F.head_fwd(M, G))
    assert r["train"] == B * 3 * (2 * (T - 1) + F.head_fwd(M, T - 1)
                                  + F.head_fwd(M, T - 1, 1))


def test_opt_cell_flops_unchanged():
    """The stage-3 cell's required FLOPs, to the integer, as the harness
    counted them before the layers' count moved to the reference."""
    cell = SP.load_cell("ppo-opt1.3b-p256g256")
    tr, m = cell.traffic, cell.config["models"]
    ref = SP.load_reference(cell.config["reference"])
    assert F.ppo_iteration(ref, m["actor"], m["critic"], tr["batch"],
                           tr["prompt_len"], tr["gen_len"]) == {
        "generate": 3338212999168, "score": 9669728796672,
        "train": 14502617579520, "total": 27510559375360}


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


def test_kernel_counts_where_v_is_narrower():
    # q.k over 6, p.v over 4: 3 pairs, 4 heads over 2 KV heads
    fl, by = F.flash_fwd(1, 2, 2, 4, 2, 6, 4)
    assert fl == 2 * 4 * 3 * (6 + 4)
    # reads q (2x4x6), k (2x2x6), v (2x2x4); writes o (2x4x4)
    assert by == 2 * (2 * 4 * 6 + 2 * 2 * 6 + 2 * 2 * 4 + 2 * 4 * 4)
    fl, by = F.flash_bwd(1, 2, 2, 4, 2, 6, 4)
    # dV and dP over 4, dQ and dK over 6
    assert fl == 2 * 4 * 3 * (4 + 4 + 6 + 6)
    # reads q, k, v, o, dO; writes dq, dk, dv
    assert by == 2 * (2 * 4 * (6 + 4 + 4 + 6) + 2 * 2 * (6 + 4 + 6 + 4))
