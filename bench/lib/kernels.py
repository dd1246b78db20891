"""Which Pallas kernel a device op of the trace is, from its operands.

On a TPU the profiler names a Pallas call after its HLO instruction
(``%closed_call.64 = ... custom-call(...),
custom_call_target="tpu_custom_call"``); its operand shapes tell the
kernels of ``repro.kernels`` apart:

- flash attention forward: q ``(B, KV, G, Lq, Dk)``, k ``(B, KV, Lk, Dk)``,
  v ``(B, KV, Lk, Dv)``;
- flash attention backward (two calls, dk/dv then dq): q and dO
  ``(B, KV, G, Lq, D)``, k, v, and the two ``(B, KV, G, Lq)`` rows;
- decode: first the integer scalar-prefetch operands, then q
  ``(B, KV, G, D)`` and k, v: the dense kernel takes lens ``(B,)`` and the
  layer index ``(1,)`` and reads the stacked arena ``(layers, B, KV, S,
  D)``; the paged kernel takes block tables ``(B, nb)`` first and reads
  the stacked pools ``(layers, blocks, KV, bs, D)``; the int8 variants
  add scale planes after v.  Kernels that read one layer's slab (rank 4)
  are told apart the same way;
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
    n_int = next((i for i, (dt, _) in enumerate(ops) if dt != "s32"),
                 len(ops))
    if n_int and ranks[n_int:n_int + 3] in ([4, 4, 4], [4, 5, 5]):
        return "paged_decode" if 2 in ranks[:n_int] else "dense_decode"
    if ranks == [2, 1]:
        return "rmsnorm"
    return "other"


def flash_shape(ops):
    """(batch, n_q, n_k, heads, kv_heads, head_dim, v_head_dim) of a flash
    call: q is the first operand, k and v the first two of rank 4 (in the
    backward dO comes between them and q)."""
    B, KV, G, Lq, D = ops[0][1]
    k, v = [s for _, s in ops if len(s) == 4][:2]
    return B, Lq, k[2], KV * G, KV, D, v[3]
