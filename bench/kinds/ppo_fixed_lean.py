"""Traffic kind ``ppo_fixed_lean``: the kind ``ppo_fixed`` (the same
program, set-up, window, traced iterations, compared numbers and
calibration) with a plain reference that holds less on the device at
once, for pairs of models whose float32 train states fill most of a chip.

``ppo_fixed``'s reference keeps, beside the actor's and the critic's
float32 parameters, a copy of each as the frozen reference and reward
models, both Adam moments, and the gradient sum next to each block's
gradients: seven float32 copies of both models at its peak.  For the
DeepSeek-V2-Lite share (743M parameters, 2.97 GB a copy) that is 20.8 GB,
more than the chip holds.  This kind computes the same reference with:

- the frozen models remade from the seed each time they score (the same
  arrays ``weights.make`` gave the program), not held;
- the gradient blocks summed into the running sum in place;
- the Adam moments kept on the host between steps.

At its peak it holds the parameters, the gradient sum and one block's
gradients: three copies (8.9 GB) and one block's activations.  The
arithmetic is ``ppo_fixed``'s, operation for operation.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.lib import flops as F
from bench.lib import spec as SP

# ``ppo_fixed``'s program, checked iterations, traced iterations and
# compared numbers; ``run`` and ``calibrate`` below are its own, with
# this module's reference
_base = SP.load_kind("ppo_fixed")
HEADS, REFERENCE_ONLY = _base.HEADS, _base.REFERENCE_ONLY
ppo_config, rollout_inputs = _base.ppo_config, _base.rollout_inputs
Program, check_steps, traced = _base.Program, _base.check_steps, _base.traced
numbers, gen_logp_shift = _base.numbers, _base.gen_logp_shift


def reference_record(cell, seed: int, rec: dict, *, mode="f32",
                     train_rows=None, rows=2) -> dict:
    """``ppo_fixed.reference_record``'s readings, held leanly."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.lib import weights as W
    from bench.references.ppo import PPOReference
    D = SP.load_reference(cell.config["reference"], cell.root)
    hp = cell.traffic["ppo"]
    models = cell.config["models"]

    def initial():
        w = W.make(models, _base.HEADS, seed, D.init)
        return w["actor"], w["critic"]

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))

    def grads(ref, pa, pc, seqs, exp):
        # PPOReference.grads, with each block's gradients added into the
        # running sum where it lies
        idx = (np.arange(len(seqs)) if train_rows is None
               else np.asarray(train_rows))
        n = jnp.float32(max(exp["mask"][idx].sum(), 1.0))
        la = lc = 0.0
        ga = gc = None
        for i in range(0, len(idx), ref.rows):
            j = idx[i:i + ref.rows]
            a, g1, c, g2 = ref._grads(
                pa, pc, jnp.asarray(seqs[j]), jnp.asarray(exp["logp"][j]),
                jnp.asarray(exp["values"][j]),
                jnp.asarray(exp["advantages"][j]),
                jnp.asarray(exp["returns"][j]), jnp.asarray(exp["mask"][j]),
                n)
            la, lc = la + float(a), lc + float(c)
            ga = g1 if ga is None else add(ga, g1)
            gc = g2 if gc is None else add(gc, g2)
            del g1, g2
        return la, ga, lc, gc

    def step(ref, params, g, host_state, lr):
        # the moments come to the device for the step and go back after
        m, v, k = host_state
        params, (m, v, k), norms = ref.adam(
            params, g, (jax.device_put(m), jax.device_put(v), k), lr)
        return params, (jax.device_get(m), jax.device_get(v), k), norms

    with jax.default_matmul_precision("highest"):
        ref = PPOReference(D, models["actor"], models["critic"], hp,
                           mode=mode, rows=rows)
        pa, pc = initial()

        def zeros(t):
            return jax.tree.map(lambda x: np.zeros(x.shape, x.dtype), t)

        sa, sc = (zeros(pa), zeros(pa), 0), (zeros(pc), zeros(pc), 0)
        out = {"losses": [], "logp": [], "values": [], "entropy": []}
        for i, (seqs, masks) in enumerate(zip(rec["seqs"], rec["masks"])):
            pa0, pc0 = initial()
            exp = ref.score(pa, pa0, pc, pc0, seqs, masks)
            del pa0, pc0
            la, ga, lc, gc = grads(ref, pa, pc, seqs, exp)
            pa, sa, na = step(ref, pa, ga, sa, hp["lr_actor"])
            del ga
            pc, sc, nc = step(ref, pc, gc, sc, hp["lr_critic"])
            del gc
            out["losses"].append((la, lc))
            for k in ("logp", "values", "entropy"):
                out[k].append(exp[k])
            out.setdefault("mask", []).append(exp["mask"])
            if i == 0:
                out["g1"] = {
                    "actor": {k: float(v) for k, v in W.leaves(na).items()},
                    "critic": {k: float(v) for k, v in W.leaves(nc).items()}}
        del sa, sc
        pa0, pc0 = initial()
        out["delta"] = {"actor": _base._delta_norms(pa, pa0),
                        "critic": _base._delta_norms(pc, pc0)}
    return out


def run(ctx) -> dict:
    """``ppo_fixed.run``: the checked iterations, the window, the traced
    iterations, then the numbers against this kind's reference."""
    cell, tr = ctx.cell, ctx.cell.traffic
    prog = Program(cell, ctx.seed)
    rec = check_steps(prog, ctx.annotate, tr["check_steps"])
    ctx.setup_done()

    spans = {"stage3.gen_s": [], "stage3.score_s": [], "stage3.train_s": []}
    i = tr["check_steps"]
    t0 = time.perf_counter()
    n = 0
    while True:
        _, _, _, (g, s, t) = prog.iteration(i, ctx.annotate)
        spans["stage3.gen_s"].append(g)
        spans["stage3.score_s"].append(s)
        spans["stage3.train_s"].append(t)
        i, n = i + 1, n + 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    window_s = time.perf_counter() - t0
    ctx.window_done()

    trace = program = None
    if ctx.trace:
        trace, program = traced(prog, i, tr["trace_iterations"],
                                ctx.tracing, ctx.annotate)
    ctx.read_memory()
    D = prog.D
    del prog
    gc.collect()

    fl = F.ppo_iteration(D, cell.config["models"]["actor"],
                         cell.config["models"]["critic"], tr["batch"],
                         tr["prompt_len"], tr["gen_len"])
    nums = numbers(rec, reference_record(cell, ctx.seed, rec))
    return {
        "attempted": n, "failed": 0,
        "end_to_end": {"ppo_iter_s": window_s / n},
        "record": {"spans": spans, "window_s": window_s, "iterations": n,
                   "flops": fl, "trace": trace, "program": program,
                   "trace_iterations": tr["trace_iterations"]},
        "numbers": nums,
    }


def calibrate(cell, seed: int, control: bool, annotate) -> dict:
    """``ppo_fixed.calibrate`` (the program's numbers; with ``control``,
    the float8 control, the half-batch and altered-token faults and the
    int8-KV decode), against this kind's reference."""
    tr = cell.traffic

    def program(**over):
        prog = Program(cell, seed, **over)
        rec = check_steps(prog, annotate, tr["check_steps"])
        del prog
        gc.collect()
        return rec

    rec = program()
    ref = reference_record(cell, seed, rec)
    out = {"program": numbers(rec, ref)}
    if not control:
        return out
    out["control_fp8"] = numbers(
        reference_record(cell, seed, rec, mode="fp8"), ref)
    out["fault_half_batch"] = numbers(reference_record(
        cell, seed, rec, train_rows=np.arange(tr["batch"] // 2)), ref)
    V = cell.config["models"]["actor"]["vocab_size"]
    altered = []
    for s, m in zip(rec["seqs"], rec["masks"]):
        a = s.copy()
        a[m] = (a[m] + 1) % V
        altered.append(a)
    out["fault_altered_token"] = numbers(
        rec, reference_record(cell, seed, dict(rec, seqs=altered)))
    del ref
    rec8 = program(kv_quant=True)
    out["decode_int8_kv"] = numbers(rec8, reference_record(cell, seed, rec8))
    return out
