"""Composable decoder transformer over scan segments.

``param_specs(cfg)`` is the single source of truth for shapes + logical
sharding axes; ``init_params`` materializes it; ``forward`` runs any of the
three phases (``full`` train/eval, ``prefill``, ``decode``) with KV/SSM
caches threaded *through* the layer scan so depth never unrolls in HLO:
prefill scans them as ``xs`` -> ``ys``, decode carries the stacked caches
so a step writes one row per layer where the stack lies.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ATTN, CROSS, SSM, ModelConfig, Segment
from repro.models import modules as M
from repro.models.modules import ParamSpec


# ===================================================================== #
# Specs
# ===================================================================== #
def _layer_specs(cfg: ModelConfig, spec) -> dict:
    D = cfg.d_model
    out = {"ln1": ParamSpec((D,), ("embed",), "ones")}
    if spec.kind == ATTN:
        out["attn"] = M.mla_specs(cfg) if cfg.mla else M.attn_specs(cfg)
        out["ln2"] = ParamSpec((D,), ("embed",), "ones")
        out["mlp"] = M.moe_specs(cfg) if spec.moe else M.mlp_specs(cfg)
    elif spec.kind == CROSS:
        out["xattn"] = M.cross_attn_specs(cfg)
        out["ln2"] = ParamSpec((D,), ("embed",), "ones")
        out["mlp"] = M.moe_specs(cfg) if spec.moe else M.mlp_specs(cfg)
    elif spec.kind == SSM:
        out["ssm"] = M.ssm_specs(cfg)
    else:
        raise ValueError(spec.kind)
    return out


def _stack(specs, n: int):
    def f(s: ParamSpec):
        return ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.scale)
    return jax.tree_util.tree_map(
        f, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def param_specs(cfg: ModelConfig) -> dict:
    D, V = cfg.d_model, cfg.vocab_size
    out = {}
    if cfg.embed_inputs:
        out["embed"] = ParamSpec((V, D), ("vocab", "embed"))
    if cfg.arch_type == "vlm":
        out["projector"] = ParamSpec((cfg.encoder_dim, D), (None, "embed"))
    out["segments"] = tuple(
        _stack(tuple(_layer_specs(cfg, ls) for ls in seg.unit_spec),
               seg.n_units)
        for seg in cfg.segments())
    out["final_norm"] = ParamSpec((D,), ("embed",), "ones")
    if not cfg.tie_embeddings:
        out["lm_head"] = ParamSpec((D, V), ("embed", "vocab"))
    return out


def init_params(cfg: ModelConfig, key) -> dict:
    return M.init_tree(param_specs(cfg), key, cfg.pdtype)


# ===================================================================== #
# Caches
# ===================================================================== #
def _layer_cache_shapes(cfg: ModelConfig, spec, batch: int, max_len: int):
    if spec.kind == ATTN:
        if cfg.mla:
            return M.mla_cache_shape(cfg, batch, max_len)
        win = spec.sliding_window or cfg.sliding_window
        return M.attn_cache_shape(cfg, batch, max_len, win)
    if spec.kind == SSM:
        return M.ssm_cache_shape(cfg, batch)
    if spec.kind == CROSS:
        kv, hd = cfg.n_kv_heads, cfg.head_dim
        return dict(xk=(batch, cfg.encoder_len, kv, hd),
                    xv=(batch, cfg.encoder_len, kv, hd))
    raise ValueError(spec.kind)


def _cache_dtype(cfg: ModelConfig, key: str):
    if key.endswith("_scale"):
        return jnp.float32
    if cfg.kv_quant and key in ("k", "v"):
        return jnp.int8
    return cfg.cdtype


def cache_struct(cfg: ModelConfig, batch: int, max_len: int):
    """ShapeDtypeStructs of the cache pytree (dry-run friendly)."""
    segs = []
    for seg in cfg.segments():
        unit = tuple(
            {k: jax.ShapeDtypeStruct((seg.n_units,) + shp,
                                     _cache_dtype(cfg, k))
             for k, shp in _layer_cache_shapes(cfg, ls, batch,
                                               max_len).items()}
            for ls in seg.unit_spec)
        segs.append(unit)
    return tuple(segs)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), cache_struct(cfg, batch,
                                                            max_len))


def paged_cache_struct(cfg: ModelConfig, num_blocks: int, block_size: int):
    """ShapeDtypeStructs of the **paged** cache pytree: each attention
    layer holds a shared ``(num_blocks, KV, block_size, D)`` block pool
    instead of a per-slot arena (block 0 is the reserved trash block;
    see :mod:`repro.serving.block_pool`).  The per-slot *block tables*
    are not part of this tree — they are layer-invariant and threaded
    through :func:`forward` as a side input.  Attention-only configs
    (no MLA / SSM / cross / sliding-window) — the serving engine
    validates this before choosing the paged layout.  With
    ``cfg.kv_quant`` the pool leaves are int8 plus fp32 ``k_scale`` /
    ``v_scale`` planes ``(num_blocks, KV, bs)``."""
    segs = []
    for seg in cfg.segments():
        unit = []
        for ls in seg.unit_spec:
            if ls.kind != ATTN or ls.sliding_window or cfg.sliding_window:
                raise NotImplementedError(
                    "paged KV cache supports full-context attention "
                    f"layers only (got {ls})")
            shapes = M.paged_attn_cache_shape(cfg, num_blocks, block_size)
            unit.append({k: jax.ShapeDtypeStruct(
                (seg.n_units,) + shp, _cache_dtype(cfg, k))
                for k, shp in shapes.items()})
        segs.append(tuple(unit))
    return tuple(segs)


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int):
    return jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        paged_cache_struct(cfg, num_blocks, block_size))


# ===================================================================== #
# Forward
# ===================================================================== #
def _in_place(cfg: ModelConfig, spec) -> bool:
    """Whether decode reads and writes this layer's cache where it lies in
    the carried stack: GQA attention arenas and paged pools.  MLA latents,
    SSM state and cross-attention K/V go through a slice of the stack."""
    return spec.kind == ATTN and not cfg.mla


def decode_layer_split(cfg: ModelConfig) -> tuple[int, int]:
    """(layers whose cache decode reads and writes in place, layers whose
    cache decode slices out and writes back)."""
    n_in = n_all = 0
    for seg in cfg.segments():
        n_in += seg.n_units * sum(_in_place(cfg, ls) for ls in seg.unit_spec)
        n_all += seg.n_units * len(seg.unit_spec)
    return n_in, n_all - n_in


def _unit_apply(cfg: ModelConfig, unit_spec, uparams, x, positions, mode,
                ucache, enc, block_tables=None, layer=None):
    """One scan unit.  In decode ``ucache`` holds the segment's stacked
    caches, the MoE layers' expert weights in ``uparams`` are stacked
    too, and ``layer`` is the unit's index into both (see
    :func:`_segment_apply`); otherwise ``ucache`` is this unit's own."""
    # barrier: stops XLA promoting the whole stacked scan carry / cache to
    # f32 outside the loop (it hoists `convert` of loop-invariant stacks,
    # materializing layer-count-sized f32 temps)
    x = jax.lax.optimization_barrier(x)
    aux = jnp.zeros((), jnp.float32)
    counts = jnp.zeros((2,), jnp.int32)
    new_caches = []
    for i, spec in enumerate(unit_spec):
        lp = uparams[i]
        lc = ucache[i] if ucache is not None else None
        sliced = layer is not None and not _in_place(cfg, spec)
        if sliced:
            lc = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, False),
                lc)
        if spec.kind == ATTN:
            h = M.rmsnorm(x, lp["ln1"], cfg.rms_eps, cfg.pallas_kernels)
            win = spec.sliding_window or cfg.sliding_window
            fn = M.mla_apply if cfg.mla else partial(M.attn_apply,
                                                     layer=layer)
            att, nc = fn(cfg, lp["attn"], h, positions=positions, mode=mode,
                         cache=lc, window=win, block_tables=block_tables)
            x = x + att
            h2 = M.rmsnorm(x, lp["ln2"], cfg.rms_eps, cfg.pallas_kernels)
            if spec.moe:
                m, a, c = M.moe_apply(cfg, lp["mlp"], h2, layer=layer)
                aux, counts = aux + a, counts + c
            else:
                m = M.mlp_apply(lp["mlp"], h2, cfg)
            x = x + m
        elif spec.kind == CROSS:
            h = M.rmsnorm(x, lp["ln1"], cfg.rms_eps, cfg.pallas_kernels)
            att, nc = M.cross_attn_apply(cfg, lp["xattn"], h, enc, mode=mode,
                                         cache=lc)
            x = x + att
            h2 = M.rmsnorm(x, lp["ln2"], cfg.rms_eps, cfg.pallas_kernels)
            if spec.moe:
                m, a, c = M.moe_apply(cfg, lp["mlp"], h2, layer=layer)
                aux, counts = aux + a, counts + c
            else:
                m = M.mlp_apply(lp["mlp"], h2, cfg)
            x = x + m
        elif spec.kind == SSM:
            h = M.rmsnorm(x, lp["ln1"], cfg.rms_eps, cfg.pallas_kernels)
            s, nc = M.ssm_apply(cfg, lp["ssm"], h, mode=mode, cache=lc)
            x = x + s
        else:
            raise ValueError(spec.kind)
        x = M.constrain_batch(x, cfg.batch_axes)
        if sliced:
            nc = jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u, layer,
                                                                 0),
                ucache[i], nc)
        new_caches.append(nc if nc is not None else {})
    return x, (aux, counts), tuple(new_caches)


def _add(a, b):
    return jax.tree.map(jnp.add, a, b)


def _aux0():
    """(aux loss, MoE counts: held slots, held experts given a slot)."""
    return jnp.zeros((), jnp.float32), jnp.zeros((2,), jnp.int32)


_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _split_experts(seg: Segment, sparams):
    """(``sparams`` without its MoE layers' expert weights, those weights
    as {unit position: {name: stack over the units}})."""
    rest, stacks = [], {}
    for i, (spec, lp) in enumerate(zip(seg.unit_spec, sparams)):
        if spec.moe:
            stacks[i] = {n: lp["mlp"][n] for n in _EXPERT_WEIGHTS}
            lp = {**lp, "mlp": {n: w for n, w in lp["mlp"].items()
                                if n not in _EXPERT_WEIGHTS}}
        rest.append(lp)
    return type(sparams)(rest), stacks


def _segment_apply(cfg: ModelConfig, seg: Segment, sparams, x, positions,
                   mode, scache, enc, block_tables=None):
    if mode == "decode":
        # the segment's stacked caches ride in the scan carry, not in
        # xs -> ys: a layer writes its new row into the stack and reads it
        # there, so a step moves one row per layer, never a whole slab or
        # the whole stack (see _in_place for the layers that still slice).
        # The expert weights stay out of xs too: each layer's grouped
        # matmuls read its experts in the whole stack, so no layer's
        # experts are sliced out into a buffer of their own.
        sparams, stacks = _split_experts(seg, sparams)

        def step(carry, xs):
            xc, aux, sc = carry
            up, li = xs
            up = type(up)({**lp, "mlp": {**lp["mlp"], **stacks[i]}}
                          if i in stacks else lp for i, lp in enumerate(up))
            xc, a, sc = _unit_apply(cfg, seg.unit_spec, up, xc, positions,
                                    mode, sc, enc, block_tables, layer=li)
            return (xc, _add(aux, a), sc), None

        (x, aux, scache), _ = jax.lax.scan(
            step, (x, _aux0(), scache),
            (sparams, jnp.arange(seg.n_units, dtype=jnp.int32)))
        return x, aux, scache

    has_cache = scache is not None

    def body(carry, xs):
        xc, aux = carry
        if has_cache:
            up, uc = xs
        else:
            up, uc = xs, None
        # block_tables is layer-invariant: captured by the scan body, not
        # threaded through the carry
        xc, a, nc = _unit_apply(cfg, seg.unit_spec, up, xc, positions, mode,
                                uc, enc, block_tables)
        return (xc, _add(aux, a)), (nc if has_cache else None)

    if cfg.remat and mode == "full":
        body = jax.checkpoint(body)
    xs = (sparams, scache) if has_cache else sparams
    (x, aux), ncache = jax.lax.scan(body, (x, _aux0()), xs)
    return x, aux, ncache


def cast_params(cfg: ModelConfig, params):
    """Compute-dtype view of the (fp32 master) params."""
    return jax.tree.map(
        lambda p: p.astype(cfg.cdtype)
        if jnp.issubdtype(p.dtype, jnp.floating) else p, params)


def forward(cfg: ModelConfig, params, *, tokens=None, embeds=None,
            encoder_embeds=None, mode: str = "full", cache=None,
            positions=None, block_tables=None, moe_counts: bool = False):
    """Returns (hidden (B,L,D), new_cache, aux_loss), and with
    ``moe_counts`` also the int32 ``(held slots, held experts given a
    slot)`` summed over the MoE layers (see :func:`moe_layers`).

    mode='full'    — training / scoring, no cache.
    mode='prefill' — like full but also fills ``cache``.
    mode='decode'  — single token step; ``positions`` is (B,1) absolute.

    ``block_tables`` ((B, nb) int32) switches decode to the **paged**
    KV layout: ``cache`` is then the shared block pool from
    :func:`init_paged_cache` and each row reads/writes through its
    table (prefill/full ignore it — paged prefill scatters a dense
    single-row prefill into pool blocks at the serving layer).
    """
    params = cast_params(cfg, params)
    if cfg.embed_inputs:
        x = jnp.take(params["embed"], tokens, axis=0)
    else:
        x = embeds
    x = M.constrain_batch(x.astype(cfg.cdtype), cfg.batch_axes)
    B, L = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(L)[None], (B, L))
    enc = None
    if cfg.arch_type == "vlm":
        enc = (encoder_embeds.astype(cfg.cdtype) @ params["projector"]
               ) if encoder_embeds is not None else None

    aux = _aux0()
    new_segs = []
    for si, seg in enumerate(cfg.segments()):
        sc = cache[si] if cache is not None else None
        x, a, nc = _segment_apply(cfg, seg, params["segments"][si], x,
                                  positions, mode, sc, enc, block_tables)
        aux = _add(aux, a)
        new_segs.append(nc)
    x = M.rmsnorm(x, params["final_norm"], cfg.rms_eps, cfg.pallas_kernels)
    new_cache = tuple(new_segs) if cache is not None else None
    if moe_counts:
        return x, new_cache, aux[0], aux[1]
    return x, new_cache, aux[0]


def moe_layers(cfg: ModelConfig) -> int:
    """Layers with a MoE MLP (each routes every token to ``cfg.top_k``
    of the router's experts)."""
    return sum(seg.n_units * sum(ls.moe for ls in seg.unit_spec)
               for seg in cfg.segments())


def lm_head(cfg: ModelConfig, params):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    head = M.wgather(head, cfg, ("embed", "vocab"))
    return head.astype(cfg.cdtype)


def logits_fn(cfg: ModelConfig, params, hidden):
    return (hidden @ lm_head(cfg, params)).astype(jnp.float32)


def lm_loss(cfg: ModelConfig, params, hidden, labels, mask):
    """Chunked cross-entropy: never materializes the full (B, L, V) logits
    when ``cfg.logit_chunk`` is set (vocabs here reach 202k)."""
    head = lm_head(cfg, params)
    B, L, D = hidden.shape
    chunk = cfg.logit_chunk or L
    chunk = min(chunk, L)
    nc = -(-L // chunk)
    pad = nc * chunk - L
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hs = hidden.reshape(B, nc, chunk, D).swapaxes(0, 1)
    ls = labels.reshape(B, nc, chunk).swapaxes(0, 1)
    ms = mask.reshape(B, nc, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def step(carry, xs):
        h, lab, m = xs
        logits = (h @ head).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * m
        return (carry[0] + nll.sum(), carry[1] + m.sum()), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())),
                                 (hs, ls, ms))
    return tot / jnp.maximum(cnt, 1.0)


def per_token_logprobs(cfg: ModelConfig, params, hidden, labels):
    """log p(labels | context) per position, chunked like lm_loss."""
    head = lm_head(cfg, params)
    B, L, D = hidden.shape
    chunk = cfg.logit_chunk or L
    chunk = min(chunk, L)
    nc = -(-L // chunk)
    pad = nc * chunk - L
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
    hs = hidden.reshape(B, nc, chunk, D).swapaxes(0, 1)
    ls = labels.reshape(B, nc, chunk).swapaxes(0, 1)

    def step(_, xs):
        h, lab = xs
        logits = (h @ head).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        return None, gold - lse

    _, lps = jax.lax.scan(step, None, (hs, ls))
    lps = lps.swapaxes(0, 1).reshape(B, nc * chunk)[:, :L]
    return lps


def count_params(cfg: ModelConfig) -> int:
    specs = param_specs(cfg)
    return int(sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))))
