"""Bring-up smoke run of the RLHF trainer and the request engine on a TPU.

    python chip_smoke.py             # one chip: kernels, trainer, server
    python chip_smoke.py --chips 4   # four chips: the mesh paths only

One chip, smollm-135m at full width (30 layers, d_model 576, 9 heads / 3
KV heads, head_dim 64, vocab 49152), random weights from a seed:

1. kernels — every main-path Pallas kernel (flash forward and backward,
   dense and paged decode in bf16 and int8, rmsnorm) against its jnp
   reference at these widths, and int8-vs-fp greedy parity of the
   paged engine on a margin-healthy suite;
2. trainer — the 3-stage pipeline through ``repro.launch.train --rlhf``
   (2 SFT steps, 2 reward steps, 2 PPO iterations at batch 8, 256 prompt
   + 256 generated tokens) on a one-chip hybrid-engine mesh, checking
   every loss is finite and counting Pallas calls in the compiled train
   step;
3. server — ragged requests through ``EngineCore`` over the paged KV
   pool, checking each finishes with tokens and counting Pallas calls in
   the compiled decode chunk;
4. device — kind, peak HBM, compile seconds and the compile-cache dir.

Four chips: 2 PPO iterations on a 2x2 DP x TP mesh against the same
iterations on one of the four chips, and a lockstep disaggregated run
(rollout 2 | train 2) against the sync run on the same split.  The model
runs in fp32 with the kernel choice left to ``ModelConfig.pallas_kernels``:
the partitioned programs (sharded PPO steps, multi-device generation
engines) must come out on the jnp code, which the phase checks, while the
unpartitioned SFT and reward steps keep the kernels.  These processes
compile afresh (see ``repro.launch.compile_cache``).

Wall times printed are bring-up timings, not benchmark results.  The last
line of standard output is one JSON object naming the device; the script
exits nonzero, with no such line, when no TPU is present or any check
fails.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-135m"
# max |kernel - reference| / max |reference| for bf16 activations
KERNEL_RTOL = 2e-2
# cross-layout (2x2 mesh vs one chip) tolerance on fp32 losses/metrics,
# |a - b| <= ATOL + RTOL * max(|a|, |b|): sharded matmuls and collectives
# reduce in another order, and some metrics (the on-policy PPO loss) sit
# at zero
MESH_RTOL, MESH_ATOL = 1e-3, 1e-5


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------- #
# compile accounting
# ---------------------------------------------------------------- #
class CompileLog:
    """Sums backend compile seconds and persistent-cache hits."""

    def __init__(self):
        from jax import monitoring
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.seconds += duration
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)


def custom_calls(jitted, *args) -> int:
    """Pallas (Mosaic) calls in the compiled program of ``jitted``."""
    return jitted.lower(*args).compile().as_text().count("tpu_custom_call")


# ---------------------------------------------------------------- #
# phase 1: kernel parity at smollm widths
# ---------------------------------------------------------------- #
def _err(jnp, out, ref):
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    abs_err = float(jnp.max(jnp.abs(out - ref)))
    scale = float(jnp.max(jnp.abs(ref)))
    check(math.isfinite(abs_err), "non-finite kernel output")
    return abs_err, abs_err / max(scale, 1e-30)


def kernel_phase(jax, cfg):
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.models import modules as M

    B, L, S, bs = 8, 512, 1024, 16
    H, KV, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bf16 = jnp.bfloat16
    ks = jax.random.split(jax.random.PRNGKey(0), 12)

    def report(name, out, ref):
        a, r = _err(jnp, out, ref)
        print(f"  {name:<28} max_abs={a:.3e} max_rel={r:.3e}")
        check(r <= KERNEL_RTOL, f"{name}: rel err {r:.3e} > {KERNEL_RTOL}")

    # flash forward and backward (the custom VJP the train step uses)
    q = jax.random.normal(ks[0], (B, L, H, D), bf16)
    k = jax.random.normal(ks[1], (B, L, KV, D), bf16)
    v = jax.random.normal(ks[2], (B, L, KV, D), bf16)
    cot = jax.random.normal(ks[3], (B, L, H, D), bf16)

    def loss(fn):
        return jax.jit(jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * cot),
            argnums=(0, 1, 2)))

    kern = loss(lambda q, k, v: ops.flash_attention(q, k, v, causal=True))
    refn = loss(lambda q, k, v: M.flash_attention(q, k, v, causal=True))
    fwd_k = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v))(q, k, v)
    fwd_r = jax.jit(lambda q, k, v: M.flash_attention(q, k, v))(q, k, v)
    report("flash_attention fwd", fwd_k, fwd_r)
    (_, gk), (_, gr) = kern(q, k, v), refn(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gk, gr):
        report(f"flash_attention bwd {name}", a, b)

    # dense decode, bf16 and int8: a stacked (2, B, KV, S, D) arena read at
    # its second layer
    qd = jax.random.normal(ks[4], (B, H, D), bf16)
    kc = jax.random.normal(ks[5], (2, B, KV, S, D), bf16)
    vc = jax.random.normal(ks[6], (2, B, KV, S, D), bf16)
    lens = jax.random.randint(ks[7], (B,), 1, S + 1)
    layer = jnp.int32(1)
    report("decode_attention",
           jax.jit(ops.decode_attention)(qd, kc, vc, lens, layer),
           jax.jit(M.decode_attention)(qd, kc, vc, lens, layer))
    ki, ksc = M._kv_quant(kc)
    vi, vsc = M._kv_quant(vc)
    report("decode_attention int8",
           jax.jit(ops.decode_attention_quant)(qd, ki, vi, ksc, vsc, lens,
                                               layer),
           jax.jit(M.decode_attention_quant)(qd, ki, vi, ksc, vsc, lens,
                                             layer))

    # paged decode, bf16 and int8: a stacked (2, nblocks, KV, bs, D) pool
    nb = S // bs
    nblocks = B * nb + 1
    kp = jax.random.normal(ks[8], (2, nblocks, KV, bs, D), bf16)
    vp = jax.random.normal(ks[9], (2, nblocks, KV, bs, D), bf16)
    tbl = (1 + jax.random.permutation(ks[10], B * nb)).reshape(B, nb)
    tbl = jnp.where(jnp.arange(nb)[None] * bs < lens[:, None], tbl, 0)
    tbl = tbl.astype(jnp.int32)
    report("paged_decode_attention",
           jax.jit(ops.paged_decode_attention)(qd, kp, vp, tbl, lens, layer),
           jax.jit(M.decode_attention_paged)(qd, kp, vp, tbl, lens, layer))
    kpi, kps = M._kv_quant(kp)
    vpi, vps = M._kv_quant(vp)
    report("paged_decode_attention int8",
           jax.jit(ops.paged_decode_attention_quant)(qd, kpi, vpi, kps, vps,
                                                     tbl, lens, layer),
           jax.jit(M.decode_attention_paged_quant)(qd, kpi, vpi, kps, vps,
                                                   tbl, lens, layer))

    # rmsnorm, forward and its VJP
    x = jax.random.normal(ks[11], (B, L, cfg.d_model), bf16)
    w = 1.0 + 0.1 * jax.random.normal(ks[0], (cfg.d_model,), jnp.float32)
    report("rmsnorm", jax.jit(lambda x, w: ops.rmsnorm(x, w))(x, w),
           jax.jit(lambda x, w: M.rmsnorm(x, w, use_pallas=False))(x, w))
    gk = jax.jit(jax.grad(lambda x, w: jnp.sum(
        ops.rmsnorm(x, w).astype(jnp.float32) ** 2), argnums=(0, 1)))(x, w)
    gr = jax.jit(jax.grad(lambda x, w: jnp.sum(
        M.rmsnorm(x, w).astype(jnp.float32) ** 2), argnums=(0, 1)))(x, w)
    report("rmsnorm bwd dx", gk[0], gr[0])
    report("rmsnorm bwd dw", gk[1], gr[1])


def int8_parity_phase(jax):
    """Greedy int8-KV vs fp32-KV streams through the paged engine on the
    tiny margin-healthy suite of tests/test_paged.py: every fp greedy
    step there has a top-2 margin above the int8 logit shift."""
    import numpy as np
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.serving.engine import GenerationEngine, Request

    V = 64
    cfg = ModelConfig(name="paged", arch_type="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=V, compute_dtype="float32", remat=False)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    reqs = [Request(uid=i, tokens=rng.integers(0, V, size=lp).astype(
                        np.int32), max_new_tokens=mn)
            for i, (lp, mn) in enumerate(zip([3, 7, 5, 4, 6, 3],
                                             [5, 8, 4, 6, 3, 7]))]

    def serve(c):
        eng = GenerationEngine(c, kv_layout="paged", block_size=4,
                               max_new_tokens=8, temperature=0.0, chunk=4)
        return {r.uid: r.tokens for r in eng.serve(
            params, reqs, jax.random.PRNGKey(9), slots=3, max_seq_len=16)}

    with jax.default_matmul_precision("highest"):
        fp, q8 = serve(cfg), serve(cfg.replace(kv_quant=True))
    same = sum(int(np.array_equal(fp[u], q8[u])) for u in fp)
    print(f"  int8-vs-fp greedy paged streams identical: {same}/{len(fp)}")
    check(same == len(fp), "int8 KV greedy streams differ from fp")


# ---------------------------------------------------------------- #
# phase 2: the 3-stage trainer through the train launcher's code
# ---------------------------------------------------------------- #
def trainer_phase(jax, cfg):
    import jax.numpy as jnp
    from functools import partial
    from repro.launch import train
    from repro.training.steps import lm_train_step
    from repro.training.train_state import TrainState

    args = train.build_parser().parse_args(
        ["--arch", ARCH, "--rlhf", "--steps", "2", "--batch", "8",
         "--seq", "512", "--max-new", "256", "--mesh", "1,1"])
    pipe, out = train.run_rlhf(args, cfg)
    losses = {"sft_loss": out["sft_loss"], "rm_loss": pipe.log["stage2"],
              "ppo_scores": out["ppo_scores"]}
    for m in pipe.log["stage3"]:
        for key in ("actor_loss", "v_loss", "reward_score"):
            if key in m:
                losses.setdefault(key, []).append(m[key])
    for key, vals in losses.items():
        print(f"  {key}: {[round(float(v), 5) for v in vals]}")
        check(len(vals) > 0 and all(math.isfinite(float(v)) for v in vals),
              f"trainer: non-finite or missing {key}")
    check(len(pipe.log["stage3"]) == 2, "trainer: expected 2 PPO iterations")
    print("  bring-up timings (not benchmark results): " + "  ".join(
        f"{k}={v:.1f}s" for k, v in out["timings"].items()))
    m0 = pipe.log["stage3"][-1]
    print(f"  stage3 last iteration: gen_tok_s={m0['gen_tok_s']:.1f} "
          f"reshard_s={m0.get('reshard_s', 0.0):.4f} "
          f"decode_steps={m0['decode_steps']:.0f} (bring-up timings)")
    check("reshard_s" in m0, "trainer: hybrid-engine reshard did not run")

    # Pallas calls in the compiled SFT train step at the run's shapes
    batch = next(iter(pipe.blender.sft_batches(8, 1)))
    batch = {k: jnp.asarray(v) for k, v in batch.items()}
    n = custom_calls(jax.jit(partial(lm_train_step, cfg)),
                     TrainState.create(pipe.e.actor_params), batch,
                     jnp.float32(1e-5))
    print(f"  tpu_custom_call in compiled train step: {n}")
    check(n > 0, "trainer: no Pallas kernel in the train step")


# ---------------------------------------------------------------- #
# phase 3: the request engine over the paged pool
# ---------------------------------------------------------------- #
def server_phase(jax, cfg):
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import build_requests
    from repro.models import transformer as T
    from repro.serving.engine import GenerationEngine

    params = T.init_params(cfg, jax.random.PRNGKey(1))
    engine = GenerationEngine(cfg, max_new_tokens=128, temperature=0.8,
                              top_k=40, chunk=16, kv_layout="paged",
                              block_size=16)
    wl = argparse.Namespace(requests=12, ragged=True, prompt_len=256,
                            max_new=128)
    reqs = build_requests(wl, cfg, np.random.default_rng(0))
    S = max(len(r.tokens) + engine.resolve(r)[3] for r in reqs)
    core = engine.core(params, jax.random.PRNGKey(2), slots=8,
                       max_seq_len=S)
    for r in reqs:
        core.add_request(r)
    got, reasons = {}, {}
    t0 = time.perf_counter()
    while core.has_work():
        for ev in core.step():
            if ev.preempted:
                got[ev.uid] = 0
                continue
            got[ev.uid] = got.get(ev.uid, 0) + ev.new_tokens.size
            if ev.finished:
                reasons[ev.uid] = ev.finish_reason
    dt = time.perf_counter() - t0
    st = core.stats()
    print(f"  {len(reasons)}/{len(reqs)} requests finished, "
          f"{sum(got.values())} tokens in {dt:.1f}s incl. compiles "
          f"(bring-up timing); blocks={st['num_blocks']} "
          f"hwm={st['block_high_water']} preempt={st['preemptions']}")
    check(len(reasons) == len(reqs), "server: not every request finished")
    check(all(got[r.uid] > 0 for r in reqs), "server: a request got no tokens")

    be = core.backend
    n = custom_calls(engine._paged_chunk_fn, core.params, core.logits,
                     be.pool, core.key, core.slot_keys, core.pos, core.done,
                     core.limit, *core.sampling_tensors(),
                     jnp.asarray(be.tables.table))
    print(f"  tpu_custom_call in compiled paged decode chunk: {n}")
    check(n > 0, "server: no Pallas kernel in the decode chunk")


# ---------------------------------------------------------------- #
# four chips: sharded PPO vs one chip, disaggregated lockstep vs sync
# ---------------------------------------------------------------- #
def _mesh_cfgs(cfg):
    # fp32 compute keeps the cross-layout comparison at reduction-order
    # noise; use_pallas stays unset so the platform rule decides
    actor = cfg.replace(n_layers=4, compute_dtype="float32")
    return actor, actor.replace(name=actor.name + "-critic")


def _check_partitioned_on_jnp(trainer, where):
    """The sharded step configs and the multi-device generation engine's
    config must resolve to the jnp code (GSPMD cannot partition a Mosaic
    kernel)."""
    got = {"actor step": trainer._step_actor_cfg.pallas_kernels,
           "critic step": trainer._step_critic_cfg.pallas_kernels,
           "generation engine": trainer.gen_engine.cfg.pallas_kernels}
    print(f"  {where} pallas_kernels: " + "  ".join(
        f"{k}={v}" for k, v in got.items()))
    check(not any(got.values()),
          f"{where}: a partitioned config chose the Pallas kernels")


def mesh_ppo_phase(jax):
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.core.hybrid_engine import HybridEngine
    from repro.core.ppo import PPOConfig, PPOTrainer
    from repro.launch.mesh import make_mesh
    from repro.models import reward as R
    from repro.models import transformer as T

    actor, critic = _mesh_cfgs(get_config(ARCH))
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    prompts = jax.random.randint(jax.random.PRNGKey(9), (8, 64), 0,
                                 actor.vocab_size)

    def run(engine, actor, critic):
        tr = PPOTrainer(actor_cfg=actor, critic_cfg=critic,
                        actor_params=T.init_params(actor, ks[0]),
                        critic_params=R.init_params(critic, ks[1]),
                        ref_params=T.init_params(actor, ks[0]),
                        reward_params=R.init_params(critic, ks[2]),
                        ppo=PPOConfig(max_new_tokens=32, temperature=0.0),
                        engine=engine)
        out = []
        key = jax.random.PRNGKey(7)
        for _ in range(2):
            key, k = jax.random.split(key)
            exp, gm = tr.generate_experience(prompts, k)
            out.append((np.asarray(exp.sequences), gm, tr.train_rlhf(exp)))
        return tr, out

    mesh = make_mesh(2, 2)
    devs = [d.id for d in mesh.devices.flat]
    print(f"  2x2 mesh devices: {devs}")
    check(len(set(devs)) == 4, "mesh: fewer than four distinct devices")
    # the one-chip reference is pinned to the jnp code the sharded
    # programs resolve to, so both sides compute the same math
    with jax.default_matmul_precision("highest"):
        _, ref = run(None, actor.replace(use_pallas=False),
                     critic.replace(use_pallas=False))
        tr, got = run(HybridEngine(actor, mesh), actor, critic)
    _check_partitioned_on_jnp(tr, "2x2")
    leaf = tr.actor.params["embed"]
    print(f"  actor embed: sharding={leaf.sharding.spec} "
          f"shards={len(leaf.addressable_shards)} on "
          f"{sorted({s.device.id for s in leaf.addressable_shards})}")
    check(len({s.device.id for s in leaf.addressable_shards}) == 4,
          "mesh: params not spread over four devices")
    worst = 0.0                          # largest |a - b| / (atol + rtol*|a|)
    for i, ((s_ref, _, m_ref), (s_got, _, m_got)) in enumerate(zip(ref,
                                                                   got)):
        same = float(np.mean(s_ref == s_got))
        print(f"  iter {i}: greedy tokens identical to one chip: "
              f"{same:.4f} of positions")
        check(same == 1.0, "mesh: greedy tokens differ from one chip")
        for k2, v in m_ref.items():
            bound = MESH_ATOL + MESH_RTOL * max(abs(v), abs(m_got[k2]))
            worst = max(worst, abs(v - m_got[k2]) / bound)
            check(abs(v - m_got[k2]) <= bound,
                  f"mesh: {k2} {m_got[k2]} vs one chip {v}")
    print(f"  losses/metrics: worst gap {worst:.3e} of the bound "
          f"|a-b| <= {MESH_ATOL} + {MESH_RTOL}*max(|a|,|b|)")


def disaggregated_phase(jax):
    import hashlib
    import numpy as np
    from repro.configs import get_config
    from repro.core import AsyncConfig, PPOConfig, RLHFEngine, RLHFPipeline
    from repro.core import StageConfig
    from repro.data import CopyTaskDataset, DataBlender
    from repro.launch.mesh import make_disaggregated_meshes

    actor, critic = _mesh_cfgs(get_config(ARCH))
    rm, tm = make_disaggregated_meshes(rollout=2, train=2)
    r_ids = [d.id for d in rm.devices.flat]
    t_ids = [d.id for d in tm.devices.flat]
    print(f"  rollout mesh devices {r_ids} | train mesh devices {t_ids}")
    check(len(set(r_ids) | set(t_ids)) == 4, "split: not four devices")
    wall = ("gen_tok_s", "reshard_s", "reshard_bytes", "publish_s",
            "publish_bytes", "queue_depth", "policy_lag", "is_ratio_mean",
            "is_ratio_max", "lockstep_fallback")

    def run(async_cfg):
        ds = [CopyTaskDataset(200, 64, 32, 256, seed=1)]
        eng = RLHFEngine(actor, critic, jax.random.PRNGKey(0), mesh=tm,
                         rollout_mesh=rm)
        pipe = RLHFPipeline(eng, DataBlender(ds, seed=0),
                            StageConfig(sft_steps=1, sft_batch=8,
                                        rm_steps=1, rm_batch=8,
                                        ppo_steps=2, ppo_batch=8),
                            PPOConfig(max_new_tokens=32, temperature=1.0),
                            async_cfg=async_cfg)
        out = pipe.run()
        return out, pipe

    def sha(tree):
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(tree):
            h.update(np.asarray(leaf).tobytes())
        return h.hexdigest()

    out_s, p_s = run(None)
    out_a, p_a = run(AsyncConfig.lockstep())
    print(f"  SFT/reward steps (unpartitioned) pallas_kernels="
          f"{actor.pallas_kernels}")
    for p in (p_s, p_a):
        _check_partitioned_on_jnp(p.trainer, "split")
    same_scores = out_s["ppo_scores"] == out_a["ppo_scores"]
    same_metrics = all(
        {k: v for k, v in ms.items() if k not in wall}
        == {k: v for k, v in ma.items() if k not in wall}
        for ms, ma in zip(p_s.log["stage3"], p_a.log["stage3"]))
    same_state = (sha(p_s.trainer.actor) == sha(p_a.trainer.actor)
                  and sha(p_s.trainer.critic) == sha(p_a.trainer.critic))
    print(f"  lockstep vs sync: scores identical={same_scores} "
          f"metrics identical={same_metrics} "
          f"actor/critic state bit-identical={same_state} "
          f"(tolerance: exact)")
    print(f"  published bytes: "
          f"{p_a.async_stats['publisher']['total_publish_bytes']}")
    check(same_scores and same_metrics and same_state,
          "disaggregated lockstep differs from sync")


# ---------------------------------------------------------------- #
def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh paths, on four chips")
    args = ap.parse_args()
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing: {e}",
              file=sys.stderr)
        return 2
    import jax

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); not running",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    log = CompileLog()
    from repro.configs import get_config
    cfg = get_config(ARCH)
    print(f"devices: {devices}")
    print(f"device_kind: {dev.device_kind}  count: {len(devices)}")
    print(f"compile cache: {cache_dir or 'off (more than one device)'}")
    print(f"model: {ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.head_dim} "
          f"vocab={cfg.vocab_size} pallas_kernels={cfg.pallas_kernels}")

    if args.chips == 4:
        phases = [("mesh PPO 2x2 vs one chip", lambda: mesh_ppo_phase(jax)),
                  ("disaggregated lockstep vs sync",
                   lambda: disaggregated_phase(jax))]
    else:
        phases = [("kernels vs jnp references", lambda: kernel_phase(jax,
                                                                     cfg)),
                  ("int8 KV greedy parity", lambda: int8_parity_phase(jax)),
                  ("trainer (3-stage RLHF)", lambda: trainer_phase(jax, cfg)),
                  ("server (EngineCore, paged KV)",
                   lambda: server_phase(jax, cfg))]
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        print(f"[phase] {name}", flush=True)
        try:
            fn()
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED in {name}: {e}", file=sys.stderr)
            return 1
        print(f"[phase] {name}: ok in {time.perf_counter() - t0:.1f}s "
              f"(bring-up timing)", flush=True)

    peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
             for d in devices[:args.chips]]
    print(f"peak HBM per chip: {[f'{p / 2**30:.2f} GiB' for p in peaks]}")
    print(f"compile: {log.compiles} backend compiles, "
          f"{log.seconds:.1f}s, persistent-cache hits {log.cache_hits}")
    print(f"total wall: {time.perf_counter() - t_all:.1f}s "
          f"(bring-up timing)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
