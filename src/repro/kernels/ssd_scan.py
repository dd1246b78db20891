"""Pallas TPU kernel for the Mamba2 SSD *intra-chunk* pass.

The SSD chunked algorithm splits work into (a) O(L·q) intra-chunk matmuls
— the compute hot spot, done here per (batch, chunk, head) grid cell with
the (q, q) decay matrix built in VMEM — and (b) an O(L/q) inter-chunk
state recurrence, which is inherently sequential and cheap, left to a
``lax.scan`` in ops.py.  This mirrors how the original CUDA SSD kernel
splits blocks, re-tiled for the MXU: the q x q decay matmul and the
q x n state outer products are both MXU-shaped when q, n are multiples
of 128/64.

Per-cell outputs: Y_diag tile, chunk end-state S_c, cumulative decays.

TPU tiling: the grid cell is (batch, chunk, head), so the kernel runs on
a head-major copy ``(b, nc, h, q, p)`` of X whose (q, p) tile is the
array's own last two dims.  ``dt`` and the cumulative decays travel as
``(b, nc, h, q)`` with every head of a chunk in one block, of which the
cell reads and writes its head's row; ``A`` sits in SMEM.  Cumulative
sums are triangular matmuls (the MXU has no scan).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HI = jax.lax.Precision.HIGHEST


def _nt(a, b):
    """a @ b.T in fp32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HI,
                               preferred_element_type=jnp.float32)


def _kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, s_ref, acs_ref):
    j = pl.program_id(2)
    x = x_ref[0, 0, 0].astype(jnp.float32)           # (q, p)
    dt = dt_ref[0, 0, pl.ds(j, 1), :].astype(jnp.float32)   # (1, q)
    A = a_ref[j]                                     # scalar (SMEM)
    B = b_ref[0, 0].astype(jnp.float32)              # (q, n)
    C = c_ref[0, 0].astype(jnp.float32)              # (q, n)
    q = x.shape[0]

    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    tri = (row >= col).astype(jnp.float32)           # tri[i, k] = k <= i
    eye = (row == col).astype(jnp.float32)

    dA = dt * A                                      # (1, q)
    a_col = _nt(tri, dA)                             # (q, 1) cumsum
    a_row = _nt(dA, tri)                             # (1, q) cumsum
    dt_col = _nt(eye, dt)                            # (q, 1)
    ldec = jnp.where(row >= col, jnp.exp(a_col - a_row), 0.0)   # (q, q)

    w = _nt(C, B) * ldec * dt                        # (q, q), xd = x * dt
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            precision=_HI,
                            preferred_element_type=jnp.float32)  # (q, p)

    a_last = jnp.sum(dA, axis=1, keepdims=True)      # (1, 1)
    coef = jnp.exp(a_last - a_col) * dt_col          # (q, 1)
    s_c = jax.lax.dot_general(x * coef, B, (((0,), (0,)), ((), ())),
                              precision=_HI,
                              preferred_element_type=jnp.float32)  # (p, n)

    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    s_ref[0, 0, 0] = s_c.astype(s_ref.dtype)
    acs_ref[0, 0, pl.ds(j, 1), :] = a_row.astype(acs_ref.dtype)


def ssd_intra_fwd(X, dt, A, B, C, *, interpret=False):
    """Intra-chunk SSD.

    X: (b, nc, q, h, p)  dt: (b, nc, q, h)  A: (h,)  B, C: (b, nc, q, n)
    Returns (Y_diag (b,nc,q,h,p), S_c (b,nc,h,p,n), A_cs (b,nc,h,q)).
    """
    b, nc, q, h, p = X.shape
    n = B.shape[-1]
    Xh = jnp.moveaxis(X, 3, 2)                       # (b, nc, h, q, p)
    dth = jnp.moveaxis(dt, 3, 2)                     # (b, nc, h, q)
    y, s_c, a_cs = pl.pallas_call(
        _kernel,
        grid=(b, nc, h),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, q, p), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, h, q), lambda i, c, j: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, c, j: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda i, c, j: (i, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, p, n), lambda i, c, j: (i, c, j, 0, 0)),
            pl.BlockSpec((1, 1, h, q), lambda i, c, j: (i, c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(Xh.shape, X.dtype),
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, q), jnp.float32),
        ],
        interpret=interpret,
    )(A.astype(jnp.float32), Xh, dth, B, C)
    return jnp.moveaxis(y, 2, 3), s_c, a_cs
