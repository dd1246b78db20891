"""Traffic kind ``ppo_fixed``: stage-3 PPO iterations on a
fixed-shape prompt batch through ``PPOTrainer`` on a one-device hybrid
engine, as ``repro.launch.train --rlhf --mesh 1,1`` builds it.

One iteration is ``generate_rollout`` -> ``score_rollout`` ->
``train_rlhf`` (actor step, critic step, EMA update), ending when every
state is ready on the device.  Set-up builds the trainer once from the
seed and drives it through the first ``check_steps`` iterations, which
compile every program the window runs; the window then runs further
iterations on the same trainer until ``--seconds`` have passed, and
``ppo_iter_s`` is the window's seconds over the iterations it completed.

The configuration file's model entries build the system's
``ModelConfig``s (``bench/lib/models.py``); its ``reference`` names the
plain reference of the models (``bench/references/<name>.py``), which
makes the weights from the seed and counts the layers' FLOPs.  Every key
of the traffic file's ``ppo`` block is a ``PPOConfig`` field or one of
``REFERENCE_ONLY``.

With ``--trace 1``, after the window, ``trace_iterations`` more
iterations run under the profiler with the program's tracer
(``repro.obs``) on; its spans and counters over them are the record's
``program``.

``correct`` compares those first iterations, once the window has closed
and the trainer is freed, with the plain float32 reference following the
same sequences (``bench/references/ppo.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict

import numpy as np

from bench.lib import compare as C
from bench.lib import flops as F
from bench.lib import weights as W
from bench.lib.models import model_config
from bench.lib.spec import SpecError, load_reference

HEADS = {"actor": "lm", "critic": "value"}
# keys of the ``ppo`` block that only the reference reads: the program's
# optimizer (``repro.training.optimizer.update``) has these as defaults
REFERENCE_ONLY = ("adam_b1", "adam_b2", "adam_eps", "grad_clip")


def ppo_config(tr, **over):
    """The program's ``PPOConfig``: the ``ppo`` block's fields by name
    (``use_ema`` on unless it says otherwise), the sampling settings from
    the traffic's top level, then ``over``."""
    from repro.core.ppo import PPOConfig
    top = {"max_new_tokens": tr["gen_len"], "temperature": tr["temperature"],
           "eos_id": None}
    fields = {f.name for f in dataclasses.fields(PPOConfig)} - set(top)
    hp = tr["ppo"]
    unknown = sorted(set(hp) - fields - set(REFERENCE_ONLY))
    if unknown:
        raise SpecError(f"traffic ppo keys {unknown} are neither PPOConfig "
                        f"fields (less {sorted(top)}, set from the top "
                        f"level) nor read by the reference "
                        f"({list(REFERENCE_ONLY)})")
    kw = {"use_ema": True, **{k: v for k, v in hp.items() if k in fields}}
    return PPOConfig(**{**kw, **top, **over})


def _leaf_norms(tree, scale=1.0) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))(
        tree)
    return {k: float(v) * scale for k, v in W.leaves(norms).items()}


def _delta_norms(new, old) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp
    d = jax.jit(lambda a, b: jax.tree.map(
        lambda x, y: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - y.astype(jnp.float32)))), a, b))(new, old)
    return {k: float(v) for k, v in W.leaves(d).items()}


def rollout_inputs(tr, vocab: int, seed: int):
    """Iteration ``i``'s prompt batch and sampling key, made on the device
    from the seed: every seed and iteration offers the same shapes."""
    import jax
    base = W.seed_key(seed)
    shape = (tr["batch"], tr["prompt_len"])
    return jax.jit(lambda i: (
        jax.random.randint(jax.random.fold_in(jax.random.fold_in(base, 1), i),
                           shape, 0, vocab, jax.numpy.int32),
        jax.random.fold_in(jax.random.fold_in(base, 2), i)))


class Program:
    """The system under test, built once from the seed."""

    def __init__(self, cell, seed: int, **ppo_over):
        from repro.core.hybrid_engine import HybridEngine
        from repro.core.ppo import PPOTrainer
        from repro.launch.mesh import make_mesh
        tr = cell.traffic
        self.cell, self.seed = cell, seed
        self.D = load_reference(cell.config["reference"], cell.root)
        self.cfgs = {r: model_config(f"{cell.config['name']}-{r}", m)
                     for r, m in cell.config["models"].items()}
        w = W.make(cell.config["models"], HEADS, seed, self.D.init)
        engine = HybridEngine(self.cfgs["actor"], make_mesh(1, 1))
        # the frozen reference and reward models start as the actor and
        # critic: the same initial arrays, which the trainer never writes
        self.trainer = PPOTrainer(
            actor_cfg=self.cfgs["actor"], critic_cfg=self.cfgs["critic"],
            actor_params=w["actor"], critic_params=w["critic"],
            ref_params=w["actor"], reward_params=w["critic"],
            ppo=ppo_config(tr, **ppo_over), engine=engine)
        self._inputs = rollout_inputs(tr, self.cfgs["actor"].vocab_size, seed)

    def iteration(self, i: int, annotate):
        import jax
        from repro import obs
        obs.step(i)
        t = self.trainer
        prompts, key = self._inputs(np.int32(i))
        t0 = time.perf_counter()
        with annotate("stage3.generate"):
            rollout, _ = t.generate_rollout(prompts, key)
        t1 = time.perf_counter()
        with annotate("stage3.score"):
            exp, _ = t.score_rollout(rollout)
            jax.block_until_ready(exp)
        t2 = time.perf_counter()
        with annotate("stage3.train"):
            metrics = t.train_rlhf(exp)
            jax.block_until_ready((t.actor, t.critic, t.ema))
        t3 = time.perf_counter()
        return rollout, exp, metrics, (t1 - t0, t2 - t1, t3 - t2)


def check_steps(prog: Program, annotate, n: int) -> dict:
    """Drive the trainer through its first ``n`` iterations and keep what
    the reference will be compared with."""
    rec = {"seqs": [], "masks": [], "logp": [], "values": [], "losses": []}
    b1 = prog.cell.traffic["ppo"]["adam_b1"]
    for i in range(n):
        rollout, exp, metrics, _ = prog.iteration(i, annotate)
        rec["seqs"].append(np.asarray(rollout.sequences))
        rec["masks"].append(np.asarray(rollout.response_mask))
        rec["logp"].append(np.asarray(exp.logprobs))
        rec["values"].append(np.asarray(exp.values))
        rec["losses"].append((metrics["actor_loss"], metrics["v_loss"]))
        if i == 0:
            # the first gradient as the optimizer got it (clipped): Adam's
            # first moment after one step is (1 - b1) * g
            t = prog.trainer
            rec["g1"] = {
                "actor": _leaf_norms(t.actor.opt.m, 1.0 / (1.0 - b1)),
                "critic": _leaf_norms(t.critic.opt.m, 1.0 / (1.0 - b1))}
        del rollout, exp
    w0 = W.make(prog.cell.config["models"], HEADS, prog.seed, prog.D.init)
    rec["delta"] = {"actor": _delta_norms(prog.trainer.actor.params,
                                          w0["actor"]),
                    "critic": _delta_norms(prog.trainer.critic.params,
                                           w0["critic"])}
    del w0
    return rec


def reference_record(cell, seed: int, rec: dict, *, mode="f32",
                     train_rows=None, rows=2) -> dict:
    """The reference following the same sequences from the same seed:
    its losses, first clipped gradient and parameter change per leaf,
    and its scores of every rollout."""
    import jax
    import jax.numpy as jnp
    from bench.references.ppo import PPOReference, zeros_like
    D = load_reference(cell.config["reference"], cell.root)
    hp = cell.traffic["ppo"]
    models = cell.config["models"]
    with jax.default_matmul_precision("highest"):
        ref = PPOReference(D, models["actor"], models["critic"], hp,
                           mode=mode, rows=rows)
        w = W.make(models, HEADS, seed, D.init)
        pa, pc = w.pop("actor"), w.pop("critic")
        pa0 = jax.tree.map(lambda x: jnp.array(x, copy=True), pa)
        pc0 = jax.tree.map(lambda x: jnp.array(x, copy=True), pc)
        sa, sc = (zeros_like(pa), zeros_like(pa), 0), \
            (zeros_like(pc), zeros_like(pc), 0)
        out = {"losses": [], "logp": [], "values": [], "entropy": []}
        for i, (seqs, masks) in enumerate(zip(rec["seqs"], rec["masks"])):
            exp = ref.score(pa, pa0, pc, pc0, seqs, masks)
            la, ga, lc, gc = ref.grads(pa, pc, seqs, exp, rows=train_rows)
            pa, sa, na = ref.adam(pa, ga, sa, hp["lr_actor"])
            pc, sc, nc = ref.adam(pc, gc, sc, hp["lr_critic"])
            del ga, gc
            out["losses"].append((la, lc))
            for k in ("logp", "values", "entropy"):
                out[k].append(exp[k])
            out.setdefault("mask", []).append(exp["mask"])
            if i == 0:
                out["g1"] = {
                    "actor": {k: float(v) for k, v in W.leaves(na).items()},
                    "critic": {k: float(v) for k, v in W.leaves(nc).items()}}
        out["delta"] = {"actor": _delta_norms(pa, pa0),
                        "critic": _delta_norms(pc, pc0)}
    return out


def traced(prog: Program, i: int, n: int, tracing, annotate):
    """Iterations ``i .. i + n - 1`` under the profiler (``tracing()``, a
    context that yields a box whose ``summary`` it fills on exit) with the
    program's tracer on: the trace's summary, and the spans and counters
    the tracer recorded over those iterations alone."""
    from repro import obs
    with tracing() as box:
        obs.reset()
        obs.enable()
        try:
            for k in range(i, i + n):
                prog.iteration(k, annotate)
        finally:
            obs.disable()
    snap = obs.snapshot()
    return box.summary, {"spans": snap["spans"],
                         "counters": snap["counters"]}


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    """The compared numbers: program (or a stand-in) against reference."""
    out = {}
    out["actor_loss_gap"] = max(abs(p[0] - r[0]) for p, r in
                                zip(prog["losses"], ref["losses"]))
    out["critic_loss_gap"] = max(abs(p[1] - r[1]) / max(abs(r[1]), 1e-30)
                                 for p, r in zip(prog["losses"],
                                                 ref["losses"]))
    out["first_grad_gap"] = max(
        C.leaf_norm_gap(prog["g1"][m], ref["g1"][m]) for m in ref["g1"])
    out["param_change_gap"] = max(
        C.leaf_norm_gap(prog["delta"][m], ref["delta"][m],
                        keep=C.moved_leaves(ref["g1"][m]))
        for m in ref["delta"])
    mask = [m > 0 for m in ref["mask"]]
    dl = np.concatenate([(p - r)[k] for p, r, k in
                         zip(prog["logp"], ref["logp"], mask)])
    out["logprob_rms_gap"] = C.rms(dl)
    dv = np.concatenate([(p - r)[k] for p, r, k in
                         zip(prog["values"], ref["values"], mask)])
    vr = np.concatenate([r[k] for r, k in zip(ref["values"], mask)])
    out["value_rms_gap"] = C.rms(dv) / max(C.rms(vr), 1e-30)
    out["gen_logp_shift"] = gen_logp_shift(ref)
    return out


def gen_logp_shift(ref: dict) -> float:
    """Mean over generated tokens of log p_ref(token) + H_ref(position):
    nought, within sampling noise, for tokens sampled from the model's own
    distribution at temperature 1; a token altered after sampling reads
    about the logits' variance below it."""
    tot, n = 0.0, 0
    for lp_ref, H, m in zip(ref["logp"], ref["entropy"], ref["mask"]):
        k = m > 0
        tot += float(np.sum(lp_ref[k] + H[k]))
        n += int(k.sum())
    return abs(tot / max(n, 1))


def run(ctx) -> dict:
    cell, tr = ctx.cell, ctx.cell.traffic
    prog = Program(cell, ctx.seed)
    rec = check_steps(prog, ctx.annotate, tr["check_steps"])
    ctx.setup_done()

    # ---------------- the measured window ---------------- #
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
    ref = reference_record(cell, ctx.seed, rec)
    nums = numbers(rec, ref)
    return {
        "attempted": n, "failed": 0,
        "end_to_end": {"ppo_iter_s": window_s / n},
        "record": {"spans": spans, "window_s": window_s, "iterations": n,
                   "flops": fl, "trace": trace, "program": program,
                   "trace_iterations": tr["trace_iterations"]},
        "numbers": nums,
    }


def calibrate(cell, seed: int, control: bool, annotate) -> dict:
    """Readings that the limits are set from (``bench/tools/calibrate.py``):
    the program's numbers against the reference on this seed and, with
    ``control``, the numbers of the control and of the faults this cell
    can have, each standing in the program's place: the reference in
    float8; the reference taking its steps on half of the batch, the mean
    over that half; the generated tokens altered after sampling.  A
    state returned unchanged reads 1 on ``param_change_gap`` by
    construction and needs no run.  Also with ``control``, the program
    with its own lower-precision decode path on (an int8 KV cache), to
    show what the numbers see of the precision of decode."""
    tr = cell.traffic
    prog = Program(cell, seed)
    rec = check_steps(prog, annotate, tr["check_steps"])
    del prog
    gc.collect()
    ref = reference_record(cell, seed, rec)
    out = {"program": numbers(rec, ref)}
    if not control:
        return out
    low = reference_record(cell, seed, rec, mode="fp8")
    out["control_fp8"] = numbers(low, ref)
    half = reference_record(cell, seed, rec,
                            train_rows=np.arange(tr["batch"] // 2))
    out["fault_half_batch"] = numbers(half, ref)
    V = cell.config["models"]["actor"]["vocab_size"]
    altered = []
    for s, m in zip(rec["seqs"], rec["masks"]):
        a = s.copy()
        a[m] = (a[m] + 1) % V
        altered.append(a)
    alt_rec = dict(rec, seqs=altered)
    alt_ref = reference_record(cell, seed, alt_rec)
    out["fault_altered_token"] = numbers(rec, alt_ref)
    del ref, low, half, alt_ref
    prog = Program(cell, seed, kv_quant=True)
    rec8 = check_steps(prog, annotate, tr["check_steps"])
    del prog
    gc.collect()
    out["decode_int8_kv"] = numbers(rec8, reference_record(cell, seed, rec8))
    return out
