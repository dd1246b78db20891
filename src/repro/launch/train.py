"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --reduced --steps 50 --batch 8 --seq 64 [--mesh 2,2] \
        [--strategy zero3] [--zero 0|1] [--lora 8] [--ckpt out/model.npz] \
        [--ckpt-dir out/ckpt --save-every 10 [--resume]]

``--ckpt-dir`` + ``--save-every`` make the run fault tolerant: every N
steps the full TrainState (params + Adam moments + step) and the data
cursor are committed through the async sharded checkpointer;
``--resume`` continues bit-identically from the latest valid
checkpoint, including across mesh topologies (docs/checkpointing.md).

On this CPU container, ``--reduced`` trains the reduced variant on
synthetic LM data end-to-end; the full configs are exercised via
``repro.launch.dryrun`` on the production mesh.

``--mesh dp,tp`` jits the train step against an explicit DP×TP device
mesh: the batch shards over ``data``, params follow ``--strategy``
(``zero3`` default: TP over ``model`` + fp32 ``embed`` dims over
``data``), and ``--zero 1`` shards the Adam moments over ``data``
(ZeRO-1) even when params are replicated.  Run locally with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to simulate the
mesh on CPU (see docs/scaling.md).

``--rlhf`` runs the full 3-stage pipeline (SFT -> RM -> PPO) instead of
the LM loop.  Stage 3 can be disaggregated and overlapped
(docs/async_rlhf.md)::

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --reduced --rlhf --async-rlhf --rollout-mesh 6 --train-mesh 2 \
        [--queue-depth 2] [--publish-every 1] [--max-lag 1] \
        [--is-ratio-abort R]

``--rollout-mesh``/``--train-mesh`` carve the host's devices into a
dedicated generation mesh and a disjoint training mesh (each flag takes
a device count or an explicit ``dp,tp``); ``--async-rlhf`` runs the
replay-queue producer/consumer loop (``--max-lag 0`` = lockstep,
bit-identical to the sync pipeline).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.core import lora as LoRA
from repro.data import CopyTaskDataset, DataBlender, SortTaskDataset
from repro.launch.mesh import (make_disaggregated_meshes, make_local_mesh,
                               mesh_from_spec)
from repro.models import transformer as T
from repro.training import checkpoint, schedules
from repro.training.steps import lm_train_step
from repro.training.train_state import TrainState


def run_rlhf(args, cfg):
    """3-stage RLHF on the reduced config; stage 3 optionally
    disaggregated (``--rollout-mesh``/``--train-mesh``) and overlapped
    (``--async-rlhf``) — see docs/async_rlhf.md."""
    from repro.core import (AsyncConfig, PPOConfig, RLHFEngine,
                            RLHFPipeline, StageConfig)
    mesh = rollout_mesh = None
    if args.rollout_mesh or args.train_mesh:
        if not (args.rollout_mesh and args.train_mesh):
            raise SystemExit("--rollout-mesh and --train-mesh go together")
        rollout_mesh, mesh = make_disaggregated_meshes(
            rollout=args.rollout_mesh, train=args.train_mesh)
        print(f"disaggregated: rollout={dict(rollout_mesh.shape)} "
              f"train={dict(mesh.shape)}")
    elif args.mesh:
        mesh = mesh_from_spec(args.mesh)
        print(f"mesh={dict(mesh.shape)}")
    async_cfg = None
    if args.async_rlhf:
        async_cfg = AsyncConfig(queue_depth=args.queue_depth,
                                publish_every=args.publish_every,
                                max_lag=args.max_lag,
                                is_ratio_abort=args.is_ratio_abort)
        print(f"async stage 3: {async_cfg}")

    half = args.seq // 2
    V = min(cfg.vocab_size, 256)
    ds = [CopyTaskDataset(10_000, half, args.seq - half, V, seed=1),
          SortTaskDataset(10_000, half, args.seq - half, V, seed=2)]
    eng = RLHFEngine(cfg, cfg.replace(name=cfg.name + "-critic"),
                     jax.random.PRNGKey(args.seed), mesh=mesh,
                     rollout_mesh=rollout_mesh)
    mgr = (checkpoint.CheckpointManager(args.ckpt_dir)
           if args.ckpt_dir else None)
    pipe = RLHFPipeline(
        eng, DataBlender(ds, seed=args.seed),
        StageConfig(sft_steps=args.steps, sft_batch=args.batch,
                    rm_steps=args.steps, rm_batch=args.batch,
                    ppo_steps=args.steps, ppo_batch=args.batch,
                    seed=args.seed),
        PPOConfig(max_new_tokens=args.max_new, temperature=1.0,
                  kv_quant=args.kv_quant),
        checkpointer=mgr, save_every=args.save_every or 1,
        async_cfg=async_cfg)
    out = pipe.run()
    t = out["timings"]
    print(f"sft_loss={out['sft_loss'][-1]:.4f}  "
          f"rm_acc={np.mean(out['rm_acc']):.2f}  "
          f"reward={out['ppo_scores'][-1]:.4f}")
    print("  ".join(f"{k}={v:.1f}s" for k, v in t.items())
          + f"  gen={pipe.gen_tok_s:.1f}tok/s")
    if pipe.async_stats:
        q = pipe.async_stats["queue"]
        print(f"async: produced={pipe.async_stats['produced']} "
              f"max_depth={q['max_depth']} dropped={q['dropped']} "
              f"fallbacks={pipe.async_stats['lockstep_fallbacks']} "
              f"publishes={pipe.async_stats['publisher']['publishes']}")
    return pipe, out


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--micro", type=int, default=1)
    ap.add_argument("--lora", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt", default=None,
                    help="final .npz params export (legacy single-file)")
    ap.add_argument("--ckpt-dir", default=None,
                    help="directory for fault-tolerant async sharded "
                         "checkpoints (see docs/checkpointing.md)")
    ap.add_argument("--save-every", type=int, default=0,
                    help="checkpoint the full TrainState + data cursor "
                         "every N steps into --ckpt-dir")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest valid checkpoint in "
                         "--ckpt-dir (bit-identical continuation)")
    ap.add_argument("--mesh", default=None,
                    help="dp,tp — jit the train step against an explicit "
                         "DP×TP mesh (e.g. 2,2)")
    ap.add_argument("--strategy", default="zero3",
                    choices=["ddp", "zero1", "zero3", "tp"],
                    help="param sharding strategy on the mesh")
    ap.add_argument("--zero", type=int, default=1, choices=[0, 1],
                    help="ZeRO stage for the Adam moments on the mesh: "
                         "1 shards them over the data axes")
    ap.add_argument("--rlhf", action="store_true",
                    help="run the 3-stage RLHF pipeline instead of the "
                         "LM loop (--steps/--batch size every stage)")
    ap.add_argument("--async-rlhf", action="store_true",
                    help="overlap stage-3 generation and training via "
                         "the replay queue (docs/async_rlhf.md)")
    ap.add_argument("--rollout-mesh", default=None,
                    help="devices for the dedicated generation mesh: a "
                         "count (TP) or an explicit 'dp,tp'")
    ap.add_argument("--train-mesh", default=None,
                    help="devices for the disjoint training mesh: a "
                         "count (DP) or an explicit 'dp,tp'")
    ap.add_argument("--queue-depth", type=int, default=2,
                    help="replay queue capacity (backpressure bound)")
    ap.add_argument("--publish-every", type=int, default=1,
                    help="publish actor weights every N PPO steps")
    ap.add_argument("--max-lag", type=int, default=1,
                    help="max behavior-policy staleness in PPO steps "
                         "(0 = lockstep, bit-identical to sync)")
    ap.add_argument("--is-ratio-abort", type=float, default=None,
                    help="importance-ratio ceiling: a stale batch whose "
                         "max ratio exceeds it drops the run to lockstep")
    ap.add_argument("--max-new", type=int, default=16,
                    help="PPO generation budget per prompt (--rlhf)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache for PPO experience generation "
                         "(--rlhf): the generation engine stores K/V as "
                         "int8 + per-row fp32 scales, training forwards "
                         "are untouched")
    return ap


def main():
    ap = build_parser()
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    if args.rlhf:
        run_rlhf(args, cfg)
        return
    mesh = None
    if args.mesh:
        if args.lora:
            ap.error("--mesh with --lora is not supported")
        mesh = mesh_from_spec(args.mesh)
        cfg = cfg.replace(batch_axes=("data",), tp_axis="model")
        print(f"mesh={dict(mesh.shape)} strategy={args.strategy} "
              f"zero={args.zero}")
    print(f"arch={cfg.name} params={cfg.n_params()/1e6:.1f}M")

    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)

    shard_batch = lambda b: b
    sharded = None
    if mesh is not None:
        from repro.training.steps import make_sharded_lm_step
        sharded = make_sharded_lm_step(cfg, mesh, args.strategy,
                                       zero=args.zero, micro=args.micro)
        shard_batch = sharded[2]

    adapters = None
    if args.lora:
        adapters = LoRA.init(params, args.lora, key)
        state = TrainState.create(adapters)
        print(f"LoRA rank={args.lora}: training "
              f"{sum(x.size for x in jax.tree.leaves(adapters))/1e6:.2f}M "
              f"adapter params")
    else:
        # with a mesh the fresh state is COMMITTED to the training
        # layout at creation — ZeRO'd fp32 moments never materialize
        # replicated (the whole point of --zero on a memory-tight mesh)
        state = TrainState.create(
            params, shardings=sharded[1] if sharded else None)

    half = args.seq // 2
    ds = [CopyTaskDataset(10_000, half, args.seq - half,
                          min(cfg.vocab_size, 256), seed=1),
          SortTaskDataset(10_000, half, args.seq - half,
                          min(cfg.vocab_size, 256), seed=2)]
    bl = DataBlender(ds, seed=args.seed)
    lr_fn = schedules.cosine_warmup(args.lr, args.steps // 10 + 1,
                                    args.steps)

    if args.lora:
        def step_fn(state, batch, lr):
            def loss(ad):
                merged = LoRA.merge(params, ad)
                from repro.training.steps import lm_loss_fn
                return lm_loss_fn(cfg, merged, batch)
            (l, met), g = jax.value_and_grad(loss, has_aux=True)(
                state.params)
            state, gn = state.apply_gradients(g, lr=lr)
            return state, dict(met, loss=l, grad_norm=gn)
        step = jax.jit(step_fn)
    else:
        step = jax.jit(lambda s, b, lr: lm_train_step(
            cfg, s, b, lr, micro=args.micro))

    mesh_ctx = None
    if sharded is not None:
        step = sharded[0]
        mesh_ctx = mesh

    mgr = None
    start = 0
    if args.ckpt_dir:
        mgr = checkpoint.CheckpointManager(args.ckpt_dir)
        if args.resume and mgr.latest_step() is not None:
            like = jax.eval_shape(lambda t: t, state)
            state, meta = mgr.restore(
                like, shardings=sharded[1] if sharded else None)
            start = int(meta["step"]) + 1
            print(f"resumed from step {meta['step']} "
                  f"(checkpoint {mgr.latest_step()})")

    t0 = time.perf_counter()
    for i, batch in enumerate(bl.sft_batches(args.batch, args.steps,
                                             skip=start), start=start):
        batch = shard_batch({k: jnp.asarray(v) for k, v in batch.items()})
        if mesh_ctx is not None:
            with mesh_ctx:
                state, m = step(state, batch, lr_fn(i))
        else:
            state, m = step(state, batch, lr_fn(i))
        if mgr is not None and args.save_every and (
                (i + 1) % args.save_every == 0 or i == args.steps - 1):
            mgr.save(i + 1, state,
                     metadata={"arch": cfg.name, "step": i})
        if i % max(args.steps // 10, 1) == 0 or i == args.steps - 1:
            dt = time.perf_counter() - t0
            print(f"step {i:4d}  loss={float(m['loss']):.4f}  "
                  f"gnorm={float(m['grad_norm']):.3f}  {dt:6.1f}s")
    if mgr is not None:
        mgr.wait_for_save()           # durable before exit
    if args.ckpt:
        tree = state.params if not args.lora else LoRA.fold(params,
                                                            state.params)
        checkpoint.save(args.ckpt, tree,
                        metadata={"arch": cfg.name, "steps": args.steps})
        print("saved", args.ckpt)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
