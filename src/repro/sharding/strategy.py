"""Sharding strategies: logical param axes -> mesh axes.

This is where the paper's parallelism vocabulary lives:

- ``ddp``   — pure data parallelism (HF-DDP baseline): params replicated,
              XLA all-reduces grads.  The paper's weakest baseline.
- ``zero1`` — params replicated, *optimizer state* sharded over data
              (ZeRO stage 1).
- ``zero3`` — params + optimizer state sharded over the data axis on the
              `embed` dimension, composed with tensor parallelism over
              `model` (ZeRO stage 3 / FSDP + TP).  Training layout.
- ``tp``    — tensor parallelism only, params replicated across data —
              the Hybrid Engine's *generation* layout: one resharding
              collective per phase instead of per-layer all-gathers per
              generated token.

Resolution is shape-aware: an axis is only sharded if its size divides the
mesh-axis product and the mesh axis is not already used by that tensor —
otherwise it silently degrades to replication (e.g. vocab=50280 is not
16-divisible and stays replicated on the model axis).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.modules import ParamSpec
from repro.models import transformer as T

STRATEGIES = ("ddp", "zero1", "zero3", "tp")

# logical axes that carry tensor-parallel shards
_TP_AXES = ("heads", "kv_heads", "mlp", "experts", "vocab")


def data_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def rules_for(strategy: str, mesh: Mesh, *, shard_params_data=None) -> dict:
    """logical axis -> mesh axis (or tuple) for parameter tensors."""
    dp = data_axes(mesh)
    tp = {a: "model" for a in _TP_AXES}
    if strategy == "ddp":
        return {}
    if strategy == "zero1":
        return {}
    if strategy == "tp":
        # Inference layout: TP over `model`, plus EXPERT PARALLELISM over
        # the `data` axis — a 100B+ MoE cannot replicate its experts
        # across data replicas (DeepSpeed-HE's TP-to-fit rationale).
        return {**tp, "experts": "data"}
    if strategy == "zero3":
        return {**tp, "embed": dp}
    raise ValueError(strategy)


def opt_rules_for(strategy: str, mesh: Mesh) -> dict:
    """Optimizer-state sharding; ZeRO-1 shards state even when params are
    replicated."""
    if strategy in ("zero1", "zero3"):
        return rules_for("zero3", mesh)
    if strategy == "tp":
        return rules_for("tp", mesh)
    return {}


def zero1_opt_rules(strategy: str, mesh: Mesh) -> dict:
    """ZeRO-1 composed with an arbitrary *param* strategy: the optimizer
    moments inherit the param layout PLUS their ``embed`` dimension
    sharded over the data axes.  Unlike :func:`opt_rules_for` (the
    historical zero1/zero3 mapping) this works for ``tp``/``ddp`` param
    layouts too — the multi-device PPO step trains with TP params
    replicated over data while the fp32 Adam moments are 1/dp-sized per
    replica."""
    dp = data_axes(mesh)
    rules = dict(rules_for(strategy, mesh))
    if dp:
        rules.setdefault("embed", dp[0] if len(dp) == 1 else dp)
    return rules


def _mesh_size(mesh: Mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def spec_to_pspec(spec: ParamSpec, rules: dict, mesh: Mesh) -> P:
    used = set()
    entries = []
    for dim, ax in zip(spec.shape, spec.axes):
        cand = rules.get(ax)
        if cand is None or ax is None or ax == "layers":
            entries.append(None)
            continue
        cand_t = (cand,) if isinstance(cand, str) else tuple(cand)
        cand_t = tuple(a for a in cand_t if a not in used)
        if cand_t and dim % _mesh_size(mesh, cand_t) == 0:
            entries.append(cand_t[0] if len(cand_t) == 1 else cand_t)
            used.update(cand_t)
        else:
            entries.append(None)
    return P(*entries)


def param_pspecs(cfg: ModelConfig, mesh: Mesh, strategy: str):
    rules = rules_for(strategy, mesh)
    specs = T.param_specs(cfg)
    return jax.tree_util.tree_map(
        lambda s: spec_to_pspec(s, rules, mesh), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def pspecs_for_tree(specs, mesh: Mesh, strategy: str, *, opt=False):
    rules = (opt_rules_for if opt else rules_for)(strategy, mesh)
    return jax.tree_util.tree_map(
        lambda s: spec_to_pspec(s, rules, mesh), specs,
        is_leaf=lambda x: isinstance(x, ParamSpec))


def param_shardings(cfg: ModelConfig, mesh: Mesh, strategy: str):
    return jax.tree_util.tree_map(lambda p: NamedSharding(mesh, p),
                                  param_pspecs(cfg, mesh, strategy))


def train_state_pspecs(cfg: ModelConfig, mesh: Mesh, strategy: str, *,
                       zero: int = 0, specs=None):
    """PartitionSpecs for a full :class:`~repro.training.train_state
    .TrainState` (params + Adam moments + step counters) under ``strategy``
    params.  ``zero=1`` additionally shards the fp32 moments over the data
    axes (ZeRO stage 1); ``zero=0`` keeps them in the param layout —
    except for the ``zero1``/``zero3`` strategies, whose NAME already
    promises sharded optimizer state, so they ignore ``zero=0`` (a
    ``zero1`` layout with replicated moments would just be ``ddp``).
    ``specs`` overrides the param-spec tree (e.g.
    ``repro.models.reward.param_specs`` for the critic's value head)."""
    from repro.training.optimizer import AdamState
    from repro.training.train_state import TrainState
    specs = T.param_specs(cfg) if specs is None else specs
    if strategy in ("zero1", "zero3"):
        zero = 1
    prules = rules_for(strategy, mesh)
    orules = zero1_opt_rules(strategy, mesh) if zero else prules

    def resolve(rules):
        return jax.tree_util.tree_map(
            lambda s: spec_to_pspec(s, rules, mesh), specs,
            is_leaf=lambda x: isinstance(x, ParamSpec))

    opt_ps = resolve(orules)
    return TrainState(params=resolve(prules),
                      opt=AdamState(m=opt_ps, v=opt_ps, step=P()),
                      step=P())


def train_state_shardings(cfg: ModelConfig, mesh: Mesh, strategy: str, *,
                          zero: int = 0, specs=None):
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p),
        train_state_pspecs(cfg, mesh, strategy, zero=zero, specs=specs))


def shardings_for_tree(specs, mesh: Mesh, strategy: str, *, opt=False):
    """NamedShardings for an arbitrary ParamSpec tree (reward/critic
    models with non-transformer heads)."""
    return jax.tree_util.tree_map(
        lambda p: NamedSharding(mesh, p),
        pspecs_for_tree(specs, mesh, strategy, opt=opt))


def cross_mesh_put(tree, shardings):
    """Place ``tree`` onto ``shardings`` that may live on a DIFFERENT
    (disjoint) device set than the inputs — the disaggregated weight
    push from the training mesh to the rollout mesh.  ``shardings=None``
    is the single-device zero-copy case.  ``device_put`` moves the
    arrays device to device; a placement it refuses is an error, not a
    reason to copy through the host."""
    if shardings is None:
        return tree
    return jax.device_put(tree, shardings)


def shard_batch(tree, mesh: Mesh):
    """Commit a batch pytree's leading dim to the data axes (replicated
    when the batch doesn't divide them).  THE one copy of the placement
    rule — the PPO trainer and the sharded LM step both call it, so the
    divisibility/replication decision can't diverge between paths."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return tree
    lead = batch_pspec(mesh, int(leaves[0].shape[0]), 1)[0]
    return jax.device_put(tree, NamedSharding(mesh, P(lead)))


def batch_pspec(mesh: Mesh, batch: int, ndim: int = 2) -> P:
    """Shard the leading (batch) axis over the data axes if divisible."""
    dp = data_axes(mesh)
    if dp and batch % _mesh_size(mesh, dp) == 0:
        lead = dp[0] if len(dp) == 1 else dp
    elif "data" in dp and batch % mesh.shape["data"] == 0:
        lead = "data"
    else:
        lead = None
    return P(lead, *([None] * (ndim - 1)))


def cache_pspecs(cache_struct_tree, mesh: Mesh, batch: int):
    """PartitionSpecs for the KV/SSM cache pytree (leading axis = scan
    units).  Batch shards over data; the KV *length* axis shards over
    `model` (kv-head counts here don't divide a 16-way model axis, so
    flash-decode runs over length shards and XLA combines the partial
    softmaxes); SSM states shard heads over `model`."""
    dp = data_axes(mesh)
    bshard = (dp[0] if len(dp) == 1 else dp) if (
        dp and batch % _mesh_size(mesh, dp) == 0) else None

    def length(n):
        return "model" if n % mesh.shape["model"] == 0 else None

    def leaf(path, s):
        key = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        shape = s.shape
        if key in ("k", "v", "k_scale", "v_scale"):  # (u, B, KV, S, ...)
            return P(None, bshard, None, length(shape[3]),
                     *([None] * (len(shape) - 4)))
        if key in ("ckv", "krope"):                  # (u, B, S, r)
            return P(None, bshard, length(shape[2]), None)
        if key == "conv":
            c_ok = shape[3] % mesh.shape["model"] == 0
            return P(None, bshard, None, "model" if c_ok else None)
        if key == "state":
            h_ok = shape[2] % mesh.shape["model"] == 0
            return P(None, bshard, "model" if h_ok else None, None, None)
        if key in ("xk", "xv"):
            return P(None, bshard, None, None, None)
        raise KeyError(key)

    return jax.tree_util.tree_map_with_path(leaf, cache_struct_tree)
