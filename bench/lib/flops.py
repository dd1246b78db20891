"""Operations and bytes that the work requires, computed from shapes.

Model FLOPs count the forward and backward matmuls and attention that
the algorithm needs, never recomputation (rematerialisation) or padding.
A multiply-add is 2 FLOPs.  Backward is twice the forward.

``m`` is a model-size dict as the configuration files hold it.  The
FLOPs of a model's layers come from the configuration's plain reference
(``bench/references/<name>.py``: ``fwd_flops(m, n_tokens, n_ctx)``),
which knows its architecture; the vocabulary and value heads, which
every model here has, are counted here (``d_model``, ``vocab_size``).
"""
from __future__ import annotations


def causal_pairs(n_q: int, n_k: int) -> int:
    """Query-key pairs a causal mask keeps when the ``n_q`` queries are
    the last ``n_q`` of ``n_k`` key positions."""
    off = n_k - n_q
    return n_q * off + n_q * (n_q + 1) // 2


def head_fwd(m, n_positions: int, width: int | None = None) -> int:
    """Output head matmul: vocabulary logits, or a value head of width 1."""
    width = m["vocab_size"] if width is None else width
    return 2 * n_positions * m["d_model"] * width


def ppo_iteration(D, actor, critic, batch: int, prompt: int, gen: int
                  ) -> dict:
    """Required FLOPs of one stage-3 iteration on a fixed-shape batch,
    split by phase; ``D`` is the configuration's reference module, which
    counts the layers (``D.fwd_flops``).

    - generate: the layers over the prompt and the first ``gen - 1``
      sampled tokens (the last token's forward feeds nothing), logits at
      the ``gen`` positions that are sampled from;
    - score: actor and reference log-probs (layers and vocabulary head on
      the ``T - 1`` predicting positions), critic values on the same
      positions, reward score at the last token (layers over all ``T``);
    - train: actor and critic forward and backward (3x forward) on the
      positions their losses read.
    """
    T = prompt + gen
    body = D.fwd_flops
    gen_f = batch * (body(actor, T - 1) + head_fwd(actor, gen))
    logp = body(actor, T - 1) + head_fwd(actor, T - 1)
    vals = body(critic, T - 1) + head_fwd(critic, T - 1, 1)
    rew = body(critic, T) + head_fwd(critic, 1, 1)
    score = batch * (2 * logp + vals + rew)
    train = batch * 3 * (logp + vals)
    return {"generate": gen_f, "score": score, "train": train,
            "total": gen_f + score + train}


# ---------------------------------------------------------------- #
# kernels: (flops, bytes) per call
# ---------------------------------------------------------------- #
def flash_fwd(batch, n_q, n_k, heads, kv_heads, head_dim, v_head_dim=None,
              itemsize=2):
    """Causal flash attention forward: QK^T over ``head_dim`` and PV over
    ``v_head_dim`` (``head_dim`` unless given) for the kept pairs; reads
    q, k, v and writes o once."""
    dv = head_dim if v_head_dim is None else v_head_dim
    flops = 2 * batch * heads * (head_dim + dv) * causal_pairs(n_q, n_k)
    bytes_ = itemsize * batch * (head_dim + dv) * (n_q * heads
                                                   + n_k * kv_heads)
    return flops, bytes_


def flash_bwd(batch, n_q, n_k, heads, kv_heads, head_dim, v_head_dim=None,
              itemsize=2):
    """Flash attention backward without recomputation: dV and dP over
    ``v_head_dim``, dQ and dK over ``head_dim``, four matmuls over the
    kept pairs (the score recompute is not counted); reads q, k, v, o, do
    and writes dq, dk, dv."""
    dv = head_dim if v_head_dim is None else v_head_dim
    flops = 4 * batch * heads * (head_dim + dv) * causal_pairs(n_q, n_k)
    bytes_ = itemsize * batch * 2 * (head_dim + dv) * (n_q * heads
                                                       + n_k * kv_heads)
    return flops, bytes_


def decode_attn(lens_sum, heads, kv_heads, head_dim, itemsize=2):
    """One decode step of attention over ``lens_sum`` cached rows summed
    over the batch: each row's k and v are read once per KV head."""
    flops = 4 * heads * head_dim * lens_sum
    bytes_ = itemsize * 2 * kv_heads * head_dim * lens_sum
    return flops, bytes_


def roofline_seconds(flops, bytes_, peak_flops, peak_bw) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak_flops, bytes_ / peak_bw)
