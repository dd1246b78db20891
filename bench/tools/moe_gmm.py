#!/usr/bin/env python3
"""Device time of the held experts' grouped matmuls on the chip, at the
shapes of the DeepSeek-V2-Lite stage-3 cell, for the implementations the
program could use: the Pallas megablox kernel
(``repro.kernels.ops.grouped_matmul``) and XLA's ragged dot
(``jax.lax.ragged_dot``).

- ``fill``: one SwiGLU block on weights of its own, with the routed rows
  filling a given share of the worst-case row bound.  A time that falls
  with the fill shows that the rows past the groups are skipped.
- ``scan``: the program's situation: a scan over the 4 MoE layers of the
  cell, the weights stacked over the layers, the rows routed by a random
  top-6 of 64 experts (8 held), for a decode step's 16 tokens (forward)
  and a training batch's 8,192 (forward and backward).  ``slice`` hands
  each layer's experts to the matmul as the scan slices them out of the
  stack; ``stack`` hands the whole stack and the layer's index
  (``repro.models.modules.grouped_matmul``), under the megablox tilings
  ``--tilings`` (tk and tn, clamped to the matrix).

    python3 bench/tools/moe_gmm.py [--only fill|scan] [--fills 0.125,0.5,1]
        [--tilings 512x512,1024x1024,2048x2048] [--calls 5]

Prints one JSON line per measurement: the device busy seconds per call
from a profiler trace (``bench.lib.trace``) and the three ops that took
most of it.  Benchmark runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as BR           # noqa: E402
from bench.lib import device as DV    # noqa: E402

D, F, E, K = 2048, 1408, 8, 6
R, LAYERS = 64, 4                            # router width, MoE layers
CASES = {"train": 16 * 512, "decode": 16}    # tokens per layer call


def block(gmm):
    def f(x, wg, wu, wd, sizes):
        h = jax.nn.silu(gmm(x, wg, sizes)) * gmm(x, wu, sizes)
        return gmm(h, wd, sizes)
    return f


def timed(fn, args, calls, **line):
    jax.block_until_ready(fn(*args))
    with BR.tracing(jax) as box:
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
    s = box.summary
    top = sorted(s.ops.items(), key=lambda kv: -kv[1][1])[:3]
    print(json.dumps({
        **line, "busy_s_per_call": s.busy_s / calls,
        "top_ops": [(n[:160], c, t / calls) for n, (c, t) in top],
    }), flush=True)


def routed_sizes(T, key):
    """(LAYERS, E) rows of each held expert: every token's top-6 of 64
    experts drawn at random, per layer."""
    import jax.numpy as jnp
    pick = jax.vmap(lambda k: jax.random.permutation(k, R)[:K])
    keys = jax.random.split(key, LAYERS * T).reshape(LAYERS, T, 2)
    chosen = jax.vmap(pick)(keys)                            # (LAYERS,T,K)
    return (chosen[..., None] == jnp.arange(E)).sum((1, 2)).astype(
        jnp.int32)


def scan_cases(calls, tilings):
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import modules as M
    key = jax.random.PRNGKey(1)
    w = [jax.random.normal(k, (LAYERS,) + s, jnp.bfloat16) * 0.02
         for k, s in zip(jax.random.split(key, 3),
                         [(E, D, F), (E, D, F), (E, F, D)])]
    sliced = {"megablox": lambda x, w, s, l: ops.grouped_matmul(x, w, s),
              "ragged_dot": lambda x, w, s, l: jax.lax.ragged_dot(x, w, s)}

    def layers(gmm, stacked):
        def f(x, wg, wu, wd, sizes):
            def body(c, xs):
                if stacked:
                    s, l = xs
                    a, b, d = wg, wu, wd
                else:
                    a, b, d, s, l = xs
                h = jax.nn.silu(gmm(c, a, s, l)) * gmm(c, b, s, l)
                return gmm(h, d, s, l).astype(c.dtype), None
            ls = jnp.arange(LAYERS)
            xs = (sizes, ls) if stacked else (wg, wu, wd, sizes, ls)
            return jax.lax.scan(body, x, xs)[0]
        return f

    variants = [(f"{n}.slice", layers(g, False), None)
                for n, g in sliced.items()]
    for t in tilings:
        tk, tn = (int(v) for v in t.split("x"))
        variants.append((f"megablox.stack.{t}", layers(
            lambda x, w, s, l: M.grouped_matmul(x, w, s, True, layer=l),
            True), (tk, tn)))
    for case, T in CASES.items():
        sizes = routed_sizes(T, jax.random.PRNGKey(T))
        x = jax.random.normal(key, (T * K, D), jnp.bfloat16)
        for name, f, tiles in variants:
            if case == "train" and tiles is not None and tiles != (512, 512):
                continue
            default = ops.gmm_tiling
            if tiles is not None:
                ops.gmm_tiling = lambda m, k, n: (
                    default(m, k, n)[0], min(tiles[0], k), min(tiles[1], n))
            try:
                fn = (jax.jit(jax.grad(
                    lambda *a: jnp.sum(f(*a).astype(jnp.float32)),
                    argnums=(0, 1, 2, 3))) if case == "train"
                      else jax.jit(f))
                timed(fn, (x, *w, sizes), calls, impl=name, case=case,
                      layers=LAYERS, rows_bound=T * K,
                      held_slots=int(sizes.sum()),
                      active_experts=int((sizes > 0).sum()))
            finally:
                ops.gmm_tiling = default


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", choices=("fill", "scan"))
    ap.add_argument("--fills", default="0.125,0.5,1")
    ap.add_argument("--tilings", default="512x512,1024x1024,2048x2048")
    ap.add_argument("--calls", type=int, default=5)
    args = ap.parse_args(argv)
    global jax
    jax = BR.configure_jax()
    import jax.numpy as jnp
    from repro.kernels import ops
    try:
        DV.require_chips(jax, 1)
    except DV.NoChip as e:
        print(f"moe_gmm: {e}", file=sys.stderr)
        return 3
    if args.only != "fill":
        scan_cases(args.calls, args.tilings.split(","))
    if args.only == "scan":
        return 0
    impls = {"megablox": ops.grouped_matmul, "ragged_dot": jax.lax.ragged_dot}
    key = jax.random.PRNGKey(0)
    w = [jax.random.normal(k, s, jnp.bfloat16) * 0.02 for k, s in zip(
        jax.random.split(key, 3), [(E, D, F), (E, D, F), (E, F, D)])]
    for case, T in CASES.items():
        M = T * K
        x = jax.random.normal(key, (M, D), jnp.bfloat16)
        for fill in (float(f) for f in args.fills.split(",")):
            rows = int(M * fill)
            sizes = jnp.asarray([rows // E + (i < rows % E) for i in range(E)],
                                jnp.int32)
            for name, gmm in impls.items():
                fwd = block(gmm)
                if case == "train":
                    fn = jax.jit(jax.grad(
                        lambda x, a, b, c, s: jnp.sum(
                            fwd(x, a, b, c, s).astype(jnp.float32)),
                        argnums=(0, 1, 2, 3)))
                else:
                    fn = jax.jit(fwd)
                timed(fn, (x, *w, sizes), args.calls, impl=name, case=case,
                      rows_bound=M, rows=rows, fill=fill)
    return 0


if __name__ == "__main__":
    sys.exit(main())
