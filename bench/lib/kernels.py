"""Which Pallas kernel a device op of the trace is, from its operands.

On a TPU the profiler names a Pallas call after its HLO instruction
(``%closed_call.64 = ... custom-call(...),
custom_call_target="tpu_custom_call"``); its operand shapes tell the
kernels of ``repro.kernels`` apart:

- flash attention forward: q ``(B, KV, G, Lq, D)``, k, v ``(B, KV, Lk, D)``;
- flash attention backward (two calls, dk/dv then dq): q and dO
  ``(B, KV, G, Lq, D)``, k, v, and the two ``(B, KV, G, Lq)`` rows;
- dense decode: lens ``(B,)``, q ``(B, KV, G, D)``, k, v ``(B, KV, S, D)``;
- paged decode: block tables ``(B, nb)``, lens ``(B,)``, q
  ``(B, KV, G, D)``, k, v pools ``(blocks, KV, bs, D)``;
- rmsnorm: x ``(N, D)``, w ``(D,)``.
"""
from __future__ import annotations

import re
from typing import List, Optional, Tuple

_SHAPE = re.compile(r"\b(bf16|f32|f16|s32|s8|u32|pred|f8e4m3fn|f8e5m2)"
                    r"\[([0-9,]*)\]")


def operands(detail: str) -> Optional[List[Tuple[str, Tuple[int, ...]]]]:
    """(dtype, shape) of each operand of a ``tpu_custom_call`` op, or None
    for any other op."""
    if 'custom_call_target="tpu_custom_call"' not in detail:
        return None
    i = detail.find("custom-call(")
    if i < 0:
        return None
    depth, j = 0, i + len("custom-call")
    for j in range(i + len("custom-call"), len(detail)):
        c = detail[j]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                break
    args = re.sub(r"\{[^{}]*\}", "", detail[i + len("custom-call("):j])
    return [(dt, tuple(int(x) for x in dims.split(",") if x))
            for dt, dims in _SHAPE.findall(args)]


def classify(ops) -> Optional[str]:
    if ops is None:
        return None
    ranks = [len(s) for _, s in ops]
    if ranks == [5, 4, 4]:
        return "flash_fwd"
    if ranks[:4] == [5, 5, 4, 4] and len(ops) == 6:
        return "flash_bwd"
    if ranks == [1, 4, 4, 4]:
        return "dense_decode"
    if ranks[:5] == [2, 1, 4, 4, 4]:
        return "paged_decode"
    if ranks == [2, 1]:
        return "rmsnorm"
    return "other"


def flash_shape(ops):
    """(batch, n_q, n_k, heads, kv_heads, head_dim) of a flash call."""
    B, KV, G, Lq, D = ops[0][1]
    Lk = ops[1][1][2]
    return B, Lq, Lk, KV * G, KV, D
