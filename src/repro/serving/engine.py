"""Serving-grade generation: early-exit decode + a stepwise request core.

The paper's Fig. 5 point is that RLHF stage-3 *experience generation*
dominates end-to-end time; the Hybrid Engine makes each decode step cheap
by resharding once per phase.  This module attacks the waste a fixed-shape
:func:`repro.serving.generate.generate` cannot avoid, and exposes the
result as a request-level serving API:

1. **Early-exit decode** (:meth:`GenerationEngine.generate`): the decode
   scan is chunked into ``chunk``-token segments dispatched from the
   host.  After each segment the (tiny) ``done`` vector is inspected and
   no further segments are dispatched once every sequence has emitted
   EOS — a batch that finishes at 40 tokens no longer pays for 256.  The
   token stream is *bit-identical* to ``generate`` (same
   :func:`repro.serving.generate.decode_scan_step` body, same PRNG-split
   sequence), so PPO sees exactly the sequences the reference path would
   have produced.

2. **Stepwise continuous batching** (:class:`EngineCore`): the vLLM-style
   ``add_request() / step()`` engine core.  A slot-based scheduler admits
   variable-length prompts into a ``slots``-wide KV cache; each slot
   carries its own absolute position, stop limit, *sampling parameters*
   and done flag.  ``step()`` runs one fused ``chunk``-step decode and
   returns :class:`StepEvent`\\ s — the newly decoded tokens per request,
   finishes (``"eos" | "length" | "cancelled"``) and preemptions — so a
   frontend can stream tokens as they decode and ``cancel()`` requests
   mid-flight (slot and KV blocks are reclaimed at the next chunk
   boundary).  :meth:`GenerationEngine.serve` remains as a thin
   drain-the-queue wrapper over the core with token streams identical to
   the historical batch-synchronous API.

Per-request sampling is *vectorized inside the jitted chunk*: the decode
graph threads ``(slots,)`` temperature / top-k / top-p / EOS tensors and
a per-slot PRNG-key lane through :func:`repro.serving.sampling.sample_rows`,
so one compiled graph serves heterogeneously-sampled requests (greedy
next to nucleus next to seeded) with zero retracing.  Requests without a
``seed`` draw from the engine's shared per-step key exactly as before —
homogeneous workloads are bit-identical to the pre-core engine — while a
seeded request draws from its own ``PRNGKey(seed)`` split chain, making
its stream reproducible independent of batch composition.

The KV cache behind the core comes in two layouts (``kv_layout``), which
are *cache backends* behind the same scheduling loop:

- ``"dense"`` — a fixed ``(slots, S)`` arena: every slot reserves
  ``max_seq_len`` KV rows for its whole lifetime.  Simple, and the
  token-identity reference for the paged layout.
- ``"paged"`` — the arena is replaced by a shared pool of fixed
  ``block_size``-token KV blocks plus per-slot *block tables*
  (vLLM-style PagedAttention; OpenRLHF adopts the same design for its
  RLHF generation phase).  A slot holds only the blocks its tokens
  occupy: prompt blocks are allocated and scattered at admission,
  decode-time blocks are appended at chunk boundaries, and all of a
  slot's blocks return to the pool when it is harvested (or cancelled).
  At an equal KV-HBM budget this admits ~``max_len / mean_len`` times
  more concurrent sequences on ragged traffic.  Admission control
  becomes "free slot AND enough free blocks for the prompt, leaving a
  ``watermark`` reserve"; if a decode-time append still finds the pool
  empty, the newest slot is preempted (blocks freed, request requeued
  at the queue front for full re-generation) so the oldest sequences
  always make progress — the scheduler cannot deadlock.  Decode
  attention walks the block table: the Pallas kernel in
  :mod:`repro.kernels.paged_attention` on TPU, a gather + dense-decode
  reference under ``jnp``.  Given the same admission order and no
  preemptions, token streams are identical to the dense layout.

  With ``prefix_cache=True`` the pool is additionally *prefix-aware*
  (the SGLang RadixAttention / vLLM automatic-prefix-caching idea):
  blocks are ref-counted and indexed by a content hash chained over
  their token prefix, admission maps the longest cached prefix into the
  slot's table as shared read-only blocks and prefills only the
  uncached suffix (each layer gathers the prefix KV and the suffix
  attends ``[prefix; suffix]`` rectangularly), and harvest parks a
  finished sequence's full blocks in an LRU instead of freeing them.
  Allocation evicts those cached blocks before the engine ever preempts
  a running slot, so enabling the cache never reduces admission.  Tail
  blocks are copied, never shared (copy-on-write): a sequence's decode
  writes start at ``prompt_len``, strictly past its shared prefix, so
  shared blocks are immutable — and the token streams are the same as
  with the cache off, given the same admission order (the suffix
  prefill recomputes exactly the logits the full prefill would have
  produced).  See :mod:`repro.serving.block_pool` for the index design
  and the one caveat (a fully allocated table's last block is never
  indexed — a finished slot's clamped post-EOS writes may wrap into
  it).

Ragged prefill correctness: prompts are right-padded to a shape bucket and
prefilled with causal attention, so real tokens never attend padding.  The
padded KV rows beyond the true prompt length are garbage, but decode
attention only exposes cache rows ``< pos + 1`` and the first decode steps
overwrite exactly those rows (row ``pos`` is written before ``pos`` becomes
visible) — the garbage is dead by construction.  The same argument covers
the paged layout, where bucket-padding rows past the prompt's last
allocated block (and post-EOS decode writes before harvest) additionally
fall through the table's trash-block padding into block 0, which nothing
reads (a finished slot with a fully allocated table wraps such writes
into its own last block instead — dead for decode, and excluded from
prefix-cache indexing at harvest so stale rows are never reused).
Architectures with recurrent state (SSM /
hybrid) cannot skip pad
tokens this way, so for them admission prefills at the exact prompt
length (one compile per distinct length instead of per bucket); they are
dense-only.
"""
from __future__ import annotations

import dataclasses
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Set

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro import obs
from repro.models import transformer as T
from repro.models.config import ATTN, ModelConfig
from repro.serving.block_pool import (TRASH_BLOCK, BlockAllocator,
                                      BlockTables, blocks_for)
from repro.serving.generate import decode_scan_step, decode_step, prefill
from repro.serving.sampling import sample, sample_rows


def count_moe(phase: str, role: str, cfg, tokens: int, counts) -> None:
    """Add one model's MoE work to the tracer's counters: the slots its
    router filled over ``tokens`` tokens (``moe.routed_slots.<phase>.
    <role>``), the slots routed to the experts held here
    (``moe.held_slots``) and, summed over the layer calls, the held
    experts that got a slot (``moe.active_experts``); ``counts`` is what
    :func:`repro.models.transformer.forward` returns for them."""
    routed = tokens * cfg.top_k * T.moe_layers(cfg)
    obs.count(f"moe.routed_slots.{phase}.{role}", routed)
    obs.count(f"moe.held_slots.{phase}.{role}", int(counts[0]))
    obs.count(f"moe.active_experts.{phase}.{role}", int(counts[1]))


class _Unset:
    """Sentinel distinguishing "not set, use the engine default" from an
    explicit ``None`` (e.g. ``eos_id=None`` = never stop on a token)."""
    def __repr__(self):
        return "<unset>"


UNSET = _Unset()


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling configuration.  Every field defaults to "use
    the engine default", so ``SamplingParams()`` reproduces the engine's
    construction-time behaviour; any mix of configurations runs through
    one jitted decode graph (the parameters are tensors, not trace
    constants).

    - ``temperature``: ``<= 0`` is greedy.
    - ``top_k`` / ``top_p``: ``0`` / ``1.0`` disable the filter.
    - ``max_new_tokens``: per-request budget override.
    - ``eos_id``: stop-token override; explicit ``None`` disables
      stopping on a token for this request even when the engine has an
      EOS configured.
    - ``seed``: when set, the request samples from its own
      ``PRNGKey(seed)`` split chain — its stream is reproducible
      regardless of what else is in the batch or when it was admitted.
      When ``None`` the request draws from the engine's shared per-step
      key (the historical behaviour; stream depends on batch
      composition).
    """
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    max_new_tokens: Optional[int] = None
    eos_id: Any = UNSET
    seed: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request: a variable-length prompt plus its budget
    and (optional) sampling parameters."""
    uid: int
    tokens: np.ndarray                 # (Lp,) int32 prompt
    max_new_tokens: Optional[int] = None
    params: SamplingParams = SamplingParams()


@dataclasses.dataclass(frozen=True)
class Completion:
    uid: int
    prompt: np.ndarray                 # (Lp,) int32
    tokens: np.ndarray                 # generated tokens, EOS included
    finish_reason: str                 # "eos" | "length" | "cancelled"


_NO_TOKENS = np.zeros((0,), np.int32)


@dataclasses.dataclass(frozen=True)
class StepEvent:
    """One per-request occurrence at a chunk boundary.

    - ``new_tokens``: tokens decoded for this request during the step
      (empty for pure state changes).
    - ``finished`` + ``finish_reason``: the request completed; its slot
      (and blocks) are already reclaimed.
    - ``preempted``: the paged pool ran dry and this request was evicted
      and requeued at the queue front — every token previously streamed
      for it is invalid and will be regenerated from scratch.
    """
    uid: int
    new_tokens: np.ndarray = dataclasses.field(
        default_factory=lambda: _NO_TOKENS)
    finished: bool = False
    finish_reason: Optional[str] = None
    preempted: bool = False


def _next_bucket(n: int, lo: int = 8) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def _pad_bucket(tokens: np.ndarray, width: int) -> np.ndarray:
    """Right-pad a 1-D token array to a (1, width) prefill batch."""
    out = np.zeros((1, width), np.int32)
    out[0, :len(tokens)] = np.asarray(tokens, np.int32)
    return out


def _scatter_row_blocks(pool, row, blk_ids, bs: int):
    """Scatter a single-row prefill cache (leaves ``(n_units, 1, KV, Lb,
    ...)``) block-wise into the paged pool at ``blk_ids`` — the one
    pool-write primitive shared by both paged admission paths.  Rows
    past the last real block land in the trash entries ``blk_ids`` is
    padded with."""
    nbp = blk_ids.shape[0]

    def scatter(pool_leaf, row_leaf):
        r = row_leaf[:, 0]                    # (n_units, KV, Lb, ...)
        pad = nbp * bs - r.shape[2]
        if pad:
            r = jnp.pad(r, ((0, 0), (0, 0), (0, pad))
                        + ((0, 0),) * (r.ndim - 3))
        r = r.reshape(r.shape[:2] + (nbp, bs) + r.shape[3:])
        # pool blocks are KV-head-major too: (n_units, nbp, KV, bs, ...)
        return pool_leaf.at[:, blk_ids].set(jnp.moveaxis(r, 2, 1))

    return jax.tree_util.tree_map(scatter, pool, row)


@dataclasses.dataclass
class _Active:
    """Host-side state of one occupied slot."""
    req: Request
    max_new: int
    eos: Optional[int]
    toks: List[int] = dataclasses.field(default_factory=list)


class GenerationEngine:
    """Engine for PPO experience generation and the serve launcher.

    Construction-time sampling settings are *defaults*: the fixed-batch
    :meth:`generate` path bakes them into its jitted decode graphs (the
    PPO hot loop), while the request-level core resolves them per request
    against each :class:`SamplingParams` and threads them through the
    chunk graph as tensors.  Params are passed per call so the Hybrid
    Engine can hand in freshly resharded actor weights every PPO
    iteration.
    """

    def __init__(self, cfg: ModelConfig, *, max_new_tokens: int,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, eos_id: Optional[int] = None,
                 chunk: int = 32, kv_layout: str = "dense",
                 block_size: int = 16, prefix_cache: bool = False,
                 mesh=None):
        # Hybrid-Engine generation layout: with a (multi-device) mesh the
        # engine consumes TP/replicated params and lays its KV cache out
        # per-device — batch rows over the `data` axis, KV length over
        # `model` where divisible (see sharding.strategy.cache_pspecs).
        # The paged block pool stays replicated (block tables are
        # host-side); None keeps every graph single-device.  A
        # multi-device engine's config is marked partitioned, which
        # ModelConfig.pallas_kernels reads.
        if mesh is not None and mesh.size > 1:
            cfg = cfg.replace(partitioned=True)
        self.cfg = cfg
        self.mesh = mesh
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.eos_id = eos_id
        self.chunk = max(1, int(chunk))
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout={kv_layout!r}")
        self.kv_layout = kv_layout
        self.block_size = max(1, int(block_size))
        if prefix_cache and kv_layout != "paged":
            raise ValueError("prefix_cache requires kv_layout='paged'")
        self.prefix_cache = bool(prefix_cache)
        # exact-length prefill for layers with recurrent state (see module
        # docstring); pure-attention stacks can use shape buckets
        self._exact_prefill = any(
            ls.kind != ATTN for seg in cfg.segments() for ls in seg.unit_spec)
        if kv_layout == "paged":
            # paged_cache_struct raises for SSM/hybrid/cross/sliding-window;
            # MLA caches compressed latents (dense-only geometry).  int8-KV
            # IS paged: the pool grows per-row scale planes that travel
            # with their blocks (see models.modules.paged_attn_cache_shape)
            if cfg.mla or cfg.arch_type == "vlm":
                raise NotImplementedError(
                    "paged KV cache supports plain-GQA token-input "
                    "decoder LMs (no MLA / VLM)")
            T.paged_cache_struct(cfg, 2, self.block_size)
        self.last_stats: dict = {}

        self._prefill_fixed = jax.jit(obs.named("gen_prefill",
                                                self._prefill_fixed_impl))
        self._chunk_fns: dict = {}        # n_steps -> jitted fixed chunk
        # donate the arena + per-slot state: every caller rebinds them from
        # the return value, and without donation each dispatch memcpys the
        # whole KV arena (args: params, tokens, length, max_new, slot,
        # arena, logits, pos, done, limit)
        self._admit_fn = jax.jit(self._admit_impl,
                                 donate_argnums=(5, 6, 7, 8, 9))
        # (params, logits, arena, key, slot_keys, pos, done, limit, temp,
        # top_k, top_p, own_key, eos) — the whole decode carry (logits,
        # arena, key, slot_keys, pos, done) is donated and rebound every
        # dispatch; the per-slot sampling tensors ride along un-donated
        # (re-uploaded from host truth, they only change at admission)
        self._serve_chunk_fn = jax.jit(self._serve_chunk_impl,
                                       donate_argnums=(1, 2, 3, 4, 5, 6))
        # paged variants: admit retraces per (bucket, prompt-block-count)
        # shape; block tables ride along un-donated (re-uploaded from the
        # host allocator's truth each dispatch)
        self._admit_paged_fn = jax.jit(self._admit_paged_impl,
                                       donate_argnums=(6, 7, 8, 9, 10))
        # prefix-cache admission: retraces per (suffix bucket, prefix
        # block count, suffix block count) shape; the gathered history
        # rides in as block ids, the pool is donated like the plain path
        self._admit_paged_prefix_fn = jax.jit(
            self._admit_paged_prefix_impl,
            donate_argnums=(8, 9, 10, 11, 12))
        self._paged_chunk_fn = jax.jit(self._paged_chunk_impl,
                                       donate_argnums=(1, 2, 3, 4, 5, 6))

    # ================================================================ #
    # mesh layout helpers (no-ops when mesh is None)
    # ================================================================ #
    def _constrain_batch_arr(self, x):
        if self.mesh is None:
            return x
        from repro.sharding import strategy as S
        ps = S.batch_pspec(self.mesh, int(x.shape[0]), x.ndim)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, ps))

    def _constrain_cache(self, cache, batch: int):
        if self.mesh is None:
            return cache
        from repro.sharding import strategy as S
        pspecs = S.cache_pspecs(cache, self.mesh, batch)
        return jax.tree_util.tree_map(
            lambda x, p: jax.lax.with_sharding_constraint(
                x, NamedSharding(self.mesh, p)), cache, pspecs)

    # ================================================================ #
    # fixed-batch path with early exit (PPO experience generation)
    # ================================================================ #
    def _prefill_fixed_impl(self, params, tokens, encoder_embeds):
        B, Lp = tokens.shape
        tokens = self._constrain_batch_arr(tokens)
        cache = self._constrain_cache(
            T.init_cache(self.cfg, B, Lp + self.max_new_tokens), B)
        logits, cache, counts = prefill(self.cfg, params, tokens, cache,
                                        encoder_embeds=encoder_embeds,
                                        moe_counts=True)
        return logits, self._constrain_cache(cache, B), counts

    def _fixed_chunk(self, n: int):
        if n not in self._chunk_fns:
            def fn(params, logits, cache, key, pos, done, encoder_embeds):
                step = decode_scan_step(
                    self.cfg, params, temperature=self.temperature,
                    top_k=self.top_k, top_p=self.top_p, eos_id=self.eos_id,
                    encoder_embeds=encoder_embeds, moe_counts=True)
                carry, (toks, was, counts) = jax.lax.scan(
                    step, (logits, cache, key, pos, done), None, length=n)
                return carry, toks, was, counts.sum(0)
            # donate the whole carry (rebound every dispatch) so chunked
            # decode never memcpys the KV cache between chunks
            self._chunk_fns[n] = jax.jit(obs.named("gen_decode", fn),
                                         donate_argnums=(1, 2, 3, 4, 5))
        return self._chunk_fns[n]

    def generate(self, params, tokens, key, *, encoder_embeds=None):
        """Drop-in for :func:`repro.serving.generate.generate` minus the
        returned cache: same ``sequences`` / ``response_mask`` contract,
        token-identical output, but decode stops dispatching once every
        sequence has emitted EOS.  ``self.last_stats`` records how many
        decode steps actually ran."""
        B, Lp = tokens.shape
        max_new = self.max_new_tokens
        if max_new == 0:
            self.last_stats = {"decode_steps": 0, "scheduled_tokens": 0,
                               "generated_tokens": 0}
            return {"sequences": tokens,
                    "response_mask": jnp.zeros((B, Lp), bool)}
        with obs.span("gen.prefill"):
            logits, cache, moe_pre = self._prefill_fixed(params, tokens,
                                                         encoder_embeds)
            pos = jnp.full((B,), Lp, jnp.int32)
            done = jnp.zeros((B,), bool)
            # the chunk fns donate their whole carry; copy the caller's
            # key so donation never invalidates an array the caller owns
            key = jnp.array(key, copy=True)

        # without an EOS there is nothing to exit early on — one fused
        # dispatch, no per-chunk host sync (the PPO default)
        chunk = self.chunk if self.eos_id is not None else max_new
        tok_parts, was_parts, moe_parts, steps = [], [], [], 0
        n_in, n_sliced = T.decode_layer_split(self.cfg)
        n_moe = T.moe_layers(self.cfg)
        with obs.span("gen.decode"):
            while steps < max_new:
                n = min(chunk, max_new - steps)
                fn = self._fixed_chunk(n)
                (logits, cache, key, pos, done), toks, was, moe = fn(
                    params, logits, cache, key, pos, done, encoder_embeds)
                moe_parts.append(moe)
                obs.count("gen.decode.inplace_layers", n_in)
                obs.count("gen.decode.copied_layers", n_sliced)
                if n_moe:
                    obs.count("moe.layers_held", n_moe)
                tok_parts.append(np.asarray(toks))
                was_parts.append(np.asarray(was))
                steps += n
                if (self.eos_id is not None and steps < max_new
                        and bool(np.asarray(done).all())):
                    break
        obs.count("gen.decode_steps", steps)

        with obs.span("gen.assemble"):
            gen = np.concatenate(tok_parts, axis=0).T      # (B, steps)
            was_done = np.concatenate(was_parts, axis=0).T
            if steps < max_new:                            # early exit: pad
                pad = max_new - steps
                gen = np.concatenate(
                    [gen, np.full((B, pad), self.eos_id, gen.dtype)], axis=1)
                was_done = np.concatenate(
                    [was_done, np.ones((B, pad), bool)], axis=1)
            sequences = np.concatenate([np.asarray(tokens), gen], axis=1)
            mask = np.concatenate(
                [np.zeros((B, Lp), bool), ~was_done], axis=1)
            if n_moe:
                # what the experts held here computed: read with the
                # tokens, which the host already waited for
                count_moe("gen", "actor", self.cfg, B * (Lp + steps),
                          np.asarray(moe_pre)
                          + sum(np.asarray(c) for c in moe_parts))
            self.last_stats = {
                "decode_steps": steps,
                "scheduled_tokens": B * steps,
                "generated_tokens": int(mask.sum()),
            }
            return {"sequences": jnp.asarray(sequences),
                    "response_mask": jnp.asarray(mask)}

    # ================================================================ #
    # admission bodies shared by both KV layouts
    # ================================================================ #
    def _prefill_row(self, params, tokens, length, row):
        """Shared admission body for both KV layouts: prefill one padded
        prompt into the single-row cache ``row``; returns the filled row
        and the logits of the TRUE last prompt token (``length`` is the
        unpadded prompt length)."""
        cfg = self.cfg
        hidden, row, _ = T.forward(cfg, params, tokens=tokens,
                                   mode="prefill", cache=row)
        h_last = hidden[0, length - 1]                     # true last token
        logit = T.logits_fn(cfg, params, h_last[None, None])[0, 0]
        return row, logit

    @staticmethod
    def _slot_reset(slot, logit, length, max_new, logits_buf, pos, done,
                    limit):
        """Reset slot ``slot``'s decode state for a fresh admission."""
        return (logits_buf.at[slot].set(logit),
                pos.at[slot].set(length),
                done.at[slot].set(False),
                limit.at[slot].set(length + max_new))

    def _admit_impl(self, params, tokens, length, max_new, slot,
                    arena, logits_buf, pos, done, limit):
        """Prefill one padded prompt into a fresh single-row cache and
        scatter it into arena slot ``slot``; reset the slot's decode
        state."""
        # single-row cache with the arena's own (S, dtype) geometry
        row = jax.tree_util.tree_map(
            lambda a: jnp.zeros((a.shape[0], 1) + a.shape[2:], a.dtype),
            arena)
        row, logit = self._prefill_row(params, tokens, length, row)
        arena = jax.tree_util.tree_map(
            lambda a, r: a.at[:, slot].set(r[:, 0]), arena, row)
        return (arena,) + self._slot_reset(slot, logit, length, max_new,
                                           logits_buf, pos, done, limit)

    def _admit_paged_impl(self, params, tokens, length, max_new, slot,
                          blk_ids, pool, logits_buf, pos, done, limit):
        """Prefill one padded prompt into a fresh dense single-row cache,
        scatter it block-wise into the pool at ``blk_ids`` (trash-padded
        past the prompt's last allocated block), and reset the slot's
        decode state.  Retraces per (bucket length, block count) shape."""
        Lb = tokens.shape[1]
        row, logit = self._prefill_row(params, tokens, length,
                                       T.init_cache(self.cfg, 1, Lb))
        pool = _scatter_row_blocks(pool, row, blk_ids, self.block_size)
        return (pool,) + self._slot_reset(slot, logit, length, max_new,
                                          logits_buf, pos, done, limit)

    def _admit_paged_prefix_impl(self, params, tokens, Ls, Lp, max_new,
                                 slot, prefix_ids, blk_ids, pool,
                                 logits_buf, pos, done, limit):
        """Prefix-cache admission: ``tokens`` is the padded UNCACHED
        suffix of the prompt (true length ``Ls``); the first
        ``len(prefix_ids) * block_size`` prompt tokens already sit in
        shared pool blocks.  The suffix prefills against that history —
        each layer gathers the prefix KV from the pool and the suffix
        attends ``[prefix; suffix]`` with rectangular causal masking —
        and its fresh KV rows scatter into the private ``blk_ids``
        blocks (trash-padded past the suffix's last allocated block).
        Shared blocks are read, never written.  Retraces per (suffix
        bucket, prefix block count, block count) shape."""
        cfg, bs = self.cfg, self.block_size
        Lb = tokens.shape[1]
        n_pre = prefix_ids.shape[0]
        P0 = n_pre * bs                       # static: cached prefix rows

        def gather(pool_leaf):                # -> (n_units, 1, P0, KV, hd)
            h = pool_leaf[:, prefix_ids]      # (n_units, n_pre, KV, bs, hd)
            h = jnp.moveaxis(h, 3, 2)         # (n_units, n_pre, bs, KV, hd)
            return h.reshape((h.shape[0], 1, P0) + h.shape[3:])

        def merge(row_t, hist_t):
            if isinstance(row_t, dict):
                out = {**row_t, "hk": hist_t["k"], "hv": hist_t["v"]}
                if "k_scale" in hist_t:       # int8 pool: scales travel too
                    out["hk_scale"] = hist_t["k_scale"]
                    out["hv_scale"] = hist_t["v_scale"]
                return out
            return tuple(merge(r, h) for r, h in zip(row_t, hist_t))

        hist = jax.tree_util.tree_map(gather, pool)
        row = merge(T.init_cache(cfg, 1, Lb), hist)
        positions = P0 + jnp.arange(Lb, dtype=jnp.int32)[None]
        hidden, row, _ = T.forward(cfg, params, tokens=tokens,
                                   mode="prefill", cache=row,
                                   positions=positions)
        h_last = hidden[0, Ls - 1]            # true last prompt token
        logit = T.logits_fn(cfg, params, h_last[None, None])[0, 0]
        pool = _scatter_row_blocks(pool, row, blk_ids, bs)
        return (pool,) + self._slot_reset(slot, logit, Lp, max_new,
                                          logits_buf, pos, done, limit)

    # ================================================================ #
    # the jitted serve chunk, shared by the dense and paged backends
    # ================================================================ #
    def _serve_step(self, params, limit, temp, top_k, top_p, own_key, eos,
                    block_tables=None):
        """Scan body shared by the dense and paged chunks: one vectorized
        sampler over per-slot parameter tensors, one shared PRNG split
        per step plus a per-slot key lane for seeded requests.  For a
        homogeneous unseeded batch the emitted stream is identical to the
        historical scalar-sampler chunk (same splits, same
        ``categorical`` call on the same filtered logits), so the two KV
        layouts — and the pre-core engine — emit identical tokens given
        identical admission order."""
        cfg = self.cfg
        pad_tok = jnp.where(eos >= 0, eos, 0).astype(jnp.int32)

        def step(carry, _):
            logits, cache, key, slot_keys, pos, done = carry
            key, sub = jax.random.split(key)
            sk = jax.vmap(jax.random.split)(slot_keys)
            slot_keys2, subs = sk[:, 0], sk[:, 1]
            tok_shared = sample_rows(logits, sub, temperature=temp,
                                     top_k=top_k, top_p=top_p)
            tok_own = sample_rows(logits, subs, temperature=temp,
                                  top_k=top_k, top_p=top_p)
            tok = jnp.where(own_key, tok_own, tok_shared)
            tok = jnp.where(done, pad_tok, tok)
            logits, cache = decode_step(cfg, params, tok, cache, pos,
                                        block_tables=block_tables)
            new_done = done | (pos + 1 >= limit) | ((eos >= 0) & (tok == eos))
            return (logits, cache, key, slot_keys2, pos + 1, new_done), \
                (tok, done)

        return step

    def _serve_chunk_impl(self, params, logits, arena, key, slot_keys, pos,
                          done, limit, temp, top_k, top_p, own_key, eos):
        """``chunk`` decode steps over the whole arena with per-slot stop
        limits (absolute position ``prompt_len + max_new_tokens``) and
        per-slot sampling tensors.  One compiled graph serves every mix
        of sampling configurations — the parameters are runtime values,
        never trace constants."""
        step = self._serve_step(params, limit, temp, top_k, top_p, own_key,
                                eos)
        carry, (toks, was) = jax.lax.scan(
            step, (logits, arena, key, slot_keys, pos, done), None,
            length=self.chunk)
        return carry, toks, was

    def _paged_chunk_impl(self, params, logits, pool, key, slot_keys, pos,
                          done, limit, temp, top_k, top_p, own_key, eos,
                          block_tables):
        """``chunk`` decode steps over the slot batch, KV read/written
        through the block tables.  Identical step body (sampler, PRNG
        splits, stop logic) to the dense chunk."""
        step = self._serve_step(params, limit, temp, top_k, top_p, own_key,
                                eos, block_tables)
        carry, (toks, was) = jax.lax.scan(
            step, (logits, pool, key, slot_keys, pos, done), None,
            length=self.chunk)
        return carry, toks, was

    # ================================================================ #
    # request-level API
    # ================================================================ #
    def resolve(self, r: Request):
        """Resolve a request's effective (temperature, top_k, top_p,
        max_new, eos, seed) against the engine defaults."""
        p = r.params or SamplingParams()
        temp = self.temperature if p.temperature is None else p.temperature
        top_k = self.top_k if p.top_k is None else p.top_k
        top_p = self.top_p if p.top_p is None else p.top_p
        if p.max_new_tokens is not None:
            max_new = p.max_new_tokens
        elif r.max_new_tokens is not None:
            max_new = r.max_new_tokens
        else:
            max_new = self.max_new_tokens
        eos = self.eos_id if p.eos_id is UNSET else p.eos_id
        return float(temp), int(top_k), float(top_p), int(max_new), eos, \
            p.seed

    def core(self, params, key, *, slots: int = 8, max_seq_len: int,
             num_blocks: Optional[int] = None,
             watermark: Optional[int] = None) -> "EngineCore":
        """Build a stepwise :class:`EngineCore` bound to ``params``."""
        return EngineCore(self, params, key, slots=slots,
                          max_seq_len=max_seq_len, num_blocks=num_blocks,
                          watermark=watermark)

    def serve(self, params, requests: Sequence[Request], key, *,
              slots: int = 8, max_seq_len: Optional[int] = None,
              num_blocks: Optional[int] = None,
              watermark: Optional[int] = None) -> List[Completion]:
        """Drain a queue of ragged requests through the stepwise core.

        A thin wrapper over :class:`EngineCore`: every request is queued
        up front, the core is stepped until idle, and the per-request
        event streams are assembled into :class:`Completion`\\ s in finish
        order.  Free slots are refilled at chunk boundaries, so each
        admitted sequence decodes alongside whatever else is in flight —
        the continuous-batching scheduler of vLLM/OpenRLHF at chunk
        granularity.  Per-sequence outputs are independent of batch
        composition (each slot attends only its own cache rows), so
        greedy results are identical to running each request alone.

        With ``kv_layout="paged"``, ``num_blocks`` sizes the shared block
        pool (default: dense-arena parity, ``slots * ceil(S / block_size)``
        usable blocks) and ``watermark`` is the free-block reserve kept by
        admission control (default: dynamic — one chunk's worth of decode
        appends per currently-running slot,
        ``n_active * ceil(chunk / block_size)``).  Both are rejected for
        the dense layout.
        """
        if self.kv_layout != "paged" and (num_blocks is not None
                                          or watermark is not None):
            raise ValueError("num_blocks/watermark require kv_layout='paged'")
        need = max((len(r.tokens) + self.resolve(r)[3] for r in requests),
                   default=1)
        S = max_seq_len or need
        if need > S:
            raise ValueError(f"max_seq_len={S} < longest request ({need})")
        core = self.core(params, key, slots=slots, max_seq_len=S,
                         num_blocks=num_blocks, watermark=watermark)
        prompts: Dict[int, np.ndarray] = {}
        for r in requests:
            core.add_request(r)
            prompts[r.uid] = np.asarray(r.tokens)
        streams: Dict[int, List[int]] = {}
        out: List[Completion] = []
        while core.has_work():
            for ev in core.step():
                if ev.preempted:
                    streams[ev.uid] = []       # regenerated from scratch
                    continue
                buf = streams.setdefault(ev.uid, [])
                buf.extend(ev.new_tokens.tolist())
                if ev.finished:
                    out.append(Completion(
                        uid=ev.uid, prompt=prompts[ev.uid],
                        tokens=np.asarray(streams.pop(ev.uid), np.int32),
                        finish_reason=ev.finish_reason))
        self.last_stats = core.stats()
        return out


# ===================================================================== #
# cache backends: the dense arena and the paged block pool present the
# same admit / prepare / dispatch / release surface to the core
# ===================================================================== #
class _DenseBackend:
    """Fixed ``(slots, S)`` KV arena: a slot owns ``S`` rows for life, so
    admission needs nothing beyond a free slot and release is free."""

    wants_seq_tokens = False           # release() ignores seq_tokens

    def __init__(self, core: "EngineCore"):
        self.core = core
        self.cache = T.init_cache(core.cfg, core.slots, core.S)
        if core.engine.mesh is not None:
            # per-device KV under the Hybrid-Engine generation layout:
            # slot rows over `data`, KV length over `model` (divisible
            # dims only — see cache_pspecs)
            from repro.sharding import strategy as S
            mesh = core.engine.mesh
            pspecs = S.cache_pspecs(self.cache, mesh, core.slots)
            self.cache = jax.device_put(self.cache, jax.tree.map(
                lambda p: NamedSharding(mesh, p), pspecs))

    def check(self, uid: int, Lp: int, max_new: int) -> None:
        if Lp + max_new > self.core.S:
            raise ValueError(
                f"request {uid} needs {Lp + max_new} KV rows > "
                f"max_seq_len={self.core.S}")

    def can_admit(self, n_prompt_tokens: int) -> bool:
        return True

    def admit(self, slot: int, tokens: np.ndarray, Lp: int,
              max_new: int) -> None:
        c, e = self.core, self.core.engine
        padded = _pad_bucket(tokens, Lp if e._exact_prefill
                             else min(_next_bucket(Lp), c.S))
        self.cache, c.logits, c.pos, c.done, c.limit = e._admit_fn(
            c.params, jnp.asarray(padded), jnp.int32(Lp),
            jnp.int32(max_new), jnp.int32(slot), self.cache, c.logits,
            c.pos, c.done, c.limit)

    def prepare_chunk(self, events: List[StepEvent]) -> None:
        pass                                   # nothing to top up

    def dispatch(self):
        c, e = self.core, self.core.engine
        (c.logits, self.cache, c.key, c.slot_keys, c.pos, c.done), toks, \
            was = e._serve_chunk_fn(
                c.params, c.logits, self.cache, c.key, c.slot_keys, c.pos,
                c.done, c.limit, *c.sampling_tensors())
        return toks, was

    def release(self, slot: int,
                seq_tokens: Optional[np.ndarray] = None) -> None:
        pass                                   # rows are reused in place

    def stats(self) -> dict:
        return {}


class _PagedBackend:
    """Block-pooled KV cache: admission allocates prompt blocks under a
    watermark reserve, every chunk boundary tops tables up to cover the
    next chunk (preempting the newest slot if the pool runs dry), and
    release returns a slot's blocks to the pool.

    With ``prefix_cache`` on, admission first matches the prompt against
    the allocator's content-hash radix index and maps the longest cached
    prefix (full blocks only) into the slot's table as shared read-only
    blocks; only the uncached suffix is prefilled — against the gathered
    prefix KV — into freshly allocated private blocks.  Tail blocks are
    copied, not shared (copy-on-write): a block the sequence will write
    into is never mapped shared, so decode appends (positions
    ``>= prompt_len``) always land strictly past the shared prefix.
    Harvest indexes a finished sequence's full blocks instead of freeing
    them (they park in the allocator's LRU once unreferenced), and
    allocation evicts those cached blocks before the engine ever
    preempts a running slot."""

    def __init__(self, core: "EngineCore", num_blocks: Optional[int],
                 watermark: Optional[int]):
        self.core = core
        e = core.engine
        bs = e.block_size
        self.nbmax = core.S // bs
        if num_blocks is None:
            num_blocks = core.slots * self.nbmax + 1   # arena parity + trash
        self.num_blocks = num_blocks
        self.alloc = BlockAllocator(num_blocks, bs)
        self.tables = BlockTables(self.alloc, core.slots, self.nbmax)
        self.watermark = watermark
        self.prefix_cache = e.prefix_cache
        # release() harvests the finished stream into the radix index
        # only when the cache is on; the core skips building it otherwise
        self.wants_seq_tokens = self.prefix_cache
        # admission reserve: ``watermark`` free blocks, or (default) one
        # chunk's worth of decode appends per *running* slot — a static
        # reserve sized by the slot cap would strangle small pools
        self.chunk_blocks = blocks_for(e.chunk, bs)
        self.pool = T.init_paged_cache(core.cfg, num_blocks, bs)
        # host mirror of pos/limit: admit sets them and every dispatched
        # chunk advances every slot by exactly ``chunk`` steps, so block
        # top-up never has to sync device state before a dispatch
        self.host_pos = [0] * core.slots
        self.host_limit = [0] * core.slots
        self.conc: List[int] = []
        self.used_samples: List[int] = []
        self.cached_prefill_tokens = 0         # prompt rows served by cache
        self.computed_prefill_tokens = 0       # prompt rows prefilled

    def check(self, uid: int, Lp: int, max_new: int) -> None:
        if Lp + max_new > self.core.S:
            raise ValueError(
                f"request {uid} needs {Lp + max_new} KV rows > "
                f"max_seq_len={self.core.S}")
        if not self.alloc.fits(Lp + max_new):
            raise ValueError(
                f"request {uid} needs "
                f"{self.alloc.blocks_for(Lp + max_new)} blocks; "
                f"pool holds {self.alloc.capacity}")

    def can_admit(self, n_prompt_tokens: int) -> bool:
        # the watermark is waived when nothing is running: the reserve
        # protects nobody and waiting would wedge the scheduler
        n_active = self.core.n_active
        reserve = (self.watermark if self.watermark is not None
                   else n_active * self.chunk_blocks)
        return self.alloc.can_admit(n_prompt_tokens, reserve=reserve,
                                    ignore_watermark=n_active == 0)

    def admit(self, slot: int, tokens: np.ndarray, Lp: int,
              max_new: int) -> None:
        c, e = self.core, self.core.engine
        bs = e.block_size
        tokens = np.asarray(tokens, np.int32)
        # one hash pass serves both the match and the insert below (the
        # chain is a prefix hash, so the full-block key list covers the
        # match's shorter one-token-short cap)
        keys = self.alloc.chunk_keys(tokens) if self.prefix_cache else None
        shared = (self.alloc.match(tokens, keys=keys)
                  if self.prefix_cache else [])
        P0 = len(shared) * bs                  # cached prefix rows
        Ls = Lp - P0                           # uncached suffix (>= 1)
        own = self.alloc.alloc(self.alloc.blocks_for(Lp) - len(shared))
        assert own is not None, "can_admit must bound admission demand"
        self.tables.assign(slot, shared + own)
        self.cached_prefill_tokens += P0
        self.computed_prefill_tokens += Ls
        padded = _pad_bucket(tokens[P0:], min(_next_bucket(Ls), c.S - P0))
        nbp = -(-padded.shape[1] // bs)        # static scatter width
        blk_ids = np.full((nbp,), TRASH_BLOCK, np.int32)
        blk_ids[:len(own)] = own
        if shared:
            self.pool, c.logits, c.pos, c.done, c.limit = \
                e._admit_paged_prefix_fn(
                    c.params, jnp.asarray(padded), jnp.int32(Ls),
                    jnp.int32(Lp), jnp.int32(max_new), jnp.int32(slot),
                    jnp.asarray(shared, jnp.int32), jnp.asarray(blk_ids),
                    self.pool, c.logits, c.pos, c.done, c.limit)
        else:
            self.pool, c.logits, c.pos, c.done, c.limit = e._admit_paged_fn(
                c.params, jnp.asarray(padded), jnp.int32(Lp),
                jnp.int32(max_new), jnp.int32(slot), jnp.asarray(blk_ids),
                self.pool, c.logits, c.pos, c.done, c.limit)
        if self.prefix_cache:
            # index the prompt's full blocks right away so batchmates —
            # PPO's k samples of one prompt, chat turns sharing a system
            # prompt — hit them even before this sequence finishes (the
            # running slot never writes them: decode appends start at
            # ``Lp``, strictly past the last full prompt block)
            self.alloc.insert(tokens, self.tables.blocks[slot], keys=keys)
        self.host_pos[slot] = Lp
        self.host_limit[slot] = Lp + max_new

    def prepare_chunk(self, events: List[StepEvent]) -> None:
        """Top up every active slot's block table to cover the next
        chunk; preempt the newest slot on pool exhaustion (the oldest
        always progresses, so the scheduler cannot deadlock)."""
        c = self.core
        active = [b for b in range(c.slots) if c.active[b] is not None]
        for b in sorted(active, key=lambda x: c.stamp[x]):
            if c.active[b] is None:              # preempted this round
                continue
            cover = min(self.host_pos[b] + c.engine.chunk,
                        self.host_limit[b])
            want = min(self.alloc.blocks_for(cover), self.nbmax)
            while not self.tables.grow(b, want):
                # evict the newest sequence overall — possibly the
                # requester itself, so an older slot is never starved
                # by a younger one
                victims = [v for v in range(c.slots)
                           if c.active[v] is not None]
                if not victims:      # unreachable: check() bounds demand
                    raise RuntimeError("paged KV pool exhausted with "
                                       "no slot to preempt")
                victim = max(victims, key=lambda v: c.stamp[v])
                c.release_slot(victim, requeue=True, events=events)
                if victim == b:
                    break

    def dispatch(self):
        c, e = self.core, self.core.engine
        self.conc.append(c.n_active)
        self.used_samples.append(self.alloc.num_live)
        (c.logits, self.pool, c.key, c.slot_keys, c.pos, c.done), toks, \
            was = e._paged_chunk_fn(
                c.params, c.logits, self.pool, c.key, c.slot_keys, c.pos,
                c.done, c.limit, *c.sampling_tensors(),
                jnp.asarray(self.tables.table))
        for b in range(c.slots):
            self.host_pos[b] += e.chunk
        return toks, was

    def release(self, slot: int,
                seq_tokens: Optional[np.ndarray] = None) -> None:
        """Drop the slot's block references.  On a normal harvest
        (``seq_tokens`` = prompt + generated stream) the sequence's full
        blocks are first indexed into the prefix cache, so they park in
        the LRU instead of the free list once unreferenced — except the
        last block of a FULLY allocated table, whose rows a finished
        slot's clamped post-EOS writes may have wrapped into (see the
        module docstring); it is never indexed."""
        if self.prefix_cache and seq_tokens is not None:
            blocks = self.tables.blocks[slot]
            n_ok = len(blocks)
            if n_ok == self.nbmax:
                n_ok -= 1
            self.alloc.insert(seq_tokens, blocks[:n_ok])
        self.tables.release(slot)

    def stats(self) -> dict:
        bs = self.core.engine.block_size
        total_prefill = (self.cached_prefill_tokens
                         + self.computed_prefill_tokens)
        return {
            "preemptions": self.core.preemptions,
            "max_concurrency": max(self.conc, default=0),
            "mean_concurrency": (float(np.mean(self.conc))
                                 if self.conc else 0.0),
            "block_size": bs,
            "num_blocks": self.num_blocks,
            "block_high_water": self.alloc.high_water,
            "mean_blocks_used": (float(np.mean(self.used_samples))
                                 if self.used_samples else 0.0),
            "kv_budget_tokens": self.alloc.capacity * bs,
            "prefix_cache": self.prefix_cache,
            "cached_prefill_tokens": self.cached_prefill_tokens,
            "computed_prefill_tokens": self.computed_prefill_tokens,
            "prefill_hit_rate": (self.cached_prefill_tokens / total_prefill
                                 if total_prefill else 0.0),
            **self.alloc.cache_stats(),
        }


# ===================================================================== #
# the stepwise core
# ===================================================================== #
class EngineCore:
    """Stepwise request-level serving core.

    The slot/admission/harvest loop shared by both KV layouts, exposed
    one chunk at a time::

        core = engine.core(params, key, slots=8, max_seq_len=256)
        core.add_request(Request(uid=0, tokens=prompt,
                                 params=SamplingParams(temperature=0.7,
                                                       top_p=0.9)))
        while core.has_work():
            for ev in core.step():          # one fused chunk of decode
                consume(ev)                 # stream tokens / finishes

    ``add_request`` queues a request (FIFO) and returns its uid;
    ``step`` admits into free slots, runs one ``chunk``-step jitted
    decode over the whole batch, and harvests the boundary into
    :class:`StepEvent`\\ s; ``cancel`` marks a request so its slot and KV
    blocks are reclaimed at the next chunk boundary.  Sampling
    parameters are per-request and threaded through the decode graph as
    tensors — admitting a greedy request next to a nucleus-sampled one
    never retraces.
    """

    def __init__(self, engine: GenerationEngine, params, key, *,
                 slots: int = 8, max_seq_len: int,
                 num_blocks: Optional[int] = None,
                 watermark: Optional[int] = None):
        cfg = engine.cfg
        if cfg.arch_type == "vlm" or not cfg.embed_inputs:
            raise NotImplementedError(
                "continuous batching supports token-input decoder LMs")
        if engine.kv_layout != "paged" and (num_blocks is not None
                                            or watermark is not None):
            raise ValueError("num_blocks/watermark require kv_layout='paged'")
        self.engine = engine
        self.cfg = cfg
        self.params = params
        self.slots = int(slots)
        S = int(max_seq_len)
        if engine.kv_layout == "paged":
            S = -(-S // engine.block_size) * engine.block_size
        self.S = S

        # device state (the donated decode carry lives here)
        self.key = jnp.array(key, copy=True)   # chunk fns donate the key
        self.logits = jnp.zeros((self.slots, cfg.vocab_size), jnp.float32)
        self.pos = jnp.zeros((self.slots,), jnp.int32)
        self.done = jnp.ones((self.slots,), bool)
        self.limit = jnp.zeros((self.slots,), jnp.int32)
        self.slot_keys = jnp.zeros((self.slots, 2), jnp.uint32)

        # host truth for the per-slot sampling tensors (uploaded each
        # dispatch; they only change at admission)
        self._temp = np.full((self.slots,), 1.0, np.float32)
        self._topk = np.zeros((self.slots,), np.int32)
        self._topp = np.ones((self.slots,), np.float32)
        self._own = np.zeros((self.slots,), bool)
        self._eos = np.full((self.slots,), -1, np.int32)

        self.queue: deque = deque()
        self.active: List[Optional[_Active]] = [None] * self.slots
        self.stamp = [0] * self.slots          # admission order, newest=max
        self._tick = 0
        self._live: Set[int] = set()           # uids queued or running
        self._cancelled: Set[int] = set()

        self.admitted = 0                      # includes re-admissions
        self.chunks = 0
        self.completed = 0
        self.gen_tokens = 0
        self.preemptions = 0

        if engine.kv_layout == "paged":
            self.backend = _PagedBackend(self, num_blocks, watermark)
        else:
            self.backend = _DenseBackend(self)

    # ---------------------------------------------------------------- #
    @property
    def n_active(self) -> int:
        return sum(a is not None for a in self.active)

    def has_work(self) -> bool:
        """Whether another :meth:`step` would make progress (requests
        queued or in flight)."""
        return bool(self.queue) or self.n_active > 0

    def sampling_tensors(self):
        """The per-slot sampling tensors, in chunk-argument order."""
        return (jnp.asarray(self._temp), jnp.asarray(self._topk),
                jnp.asarray(self._topp), jnp.asarray(self._own),
                jnp.asarray(self._eos))

    def add_request(self, r: Request) -> int:
        """Queue a request (FIFO).  Validates that it can ever run under
        this core's geometry; returns its uid (the cancel handle)."""
        if r.uid in self._live:
            raise ValueError(f"uid {r.uid} is already queued or running")
        _, _, _, max_new, _, _ = self.engine.resolve(r)
        if max_new > 0:
            self.backend.check(r.uid, len(r.tokens), max_new)
        self.queue.append(r)
        self._live.add(r.uid)
        return r.uid

    def cancel(self, uid: int) -> bool:
        """Cancel a queued or in-flight request.  Reclamation (slot,
        blocks) happens at the next chunk boundary, where :meth:`step`
        emits a ``finish_reason="cancelled"`` event.  Returns whether the
        uid was live."""
        if uid not in self._live:
            return False
        self._cancelled.add(uid)
        return True

    # ---------------------------------------------------------------- #
    def release_slot(self, b: int, *, requeue: bool,
                     events: Optional[List[StepEvent]] = None,
                     seq_tokens: Optional[np.ndarray] = None) -> None:
        """Free slot ``b`` (blocks back to the pool — or, on a harvest
        with the prefix cache enabled, into the cache LRU — under the
        paged backend); optionally requeue its request at the queue
        front (preemption).  The slot's device state keeps decoding
        garbage (dense: into its own arena row; paged: into the trash
        block) until the next admission resets it — nothing reads it."""
        a = self.active[b]
        self.backend.release(b, seq_tokens)
        if requeue and a is not None:
            self.queue.appendleft(a.req)
            self.preemptions += 1
            if events is not None:
                events.append(StepEvent(uid=a.req.uid, preempted=True))
        self.active[b] = None

    def _finish(self, b: int, new: np.ndarray, reason: str,
                events: List[StepEvent]) -> None:
        a = self.active[b]
        self.gen_tokens += len(a.toks)
        self.completed += 1
        self._live.discard(a.req.uid)
        events.append(StepEvent(uid=a.req.uid, new_tokens=new,
                                finished=True, finish_reason=reason))
        # harvest the finished stream into the prefix cache (the prompt's
        # blocks were indexed at admission; this adds the generated
        # region's full blocks — a cancelled stream is harvested too,
        # its blocks hold exactly ``prompt + streamed`` rows).  Only the
        # prefix-caching backend reads the concatenation; the common
        # path skips building it.
        seq = None
        if self.backend.wants_seq_tokens:
            seq = np.concatenate([np.asarray(a.req.tokens, np.int32),
                                  np.asarray(a.toks, np.int32)])
        self.release_slot(b, requeue=False, seq_tokens=seq)

    def _process_cancels(self, events: List[StepEvent]) -> None:
        if not self._cancelled:
            return
        kept: deque = deque()
        for r in self.queue:                   # cancelled before admission
            if r.uid in self._cancelled:
                self._cancelled.discard(r.uid)
                self._live.discard(r.uid)
                self.completed += 1
                events.append(StepEvent(uid=r.uid, finished=True,
                                        finish_reason="cancelled"))
            else:
                kept.append(r)
        self.queue = kept
        for b in range(self.slots):            # cancelled mid-flight
            a = self.active[b]
            if a is None or a.req.uid not in self._cancelled:
                continue
            self._cancelled.discard(a.req.uid)
            # stop the lane from decoding garbage until the slot refills
            self.done = self.done.at[b].set(True)
            self._finish(b, _NO_TOKENS, "cancelled", events)

    def _admit_phase(self, events: List[StepEvent]) -> None:
        for b in range(self.slots):
            if self.active[b] is not None:
                continue
            r = None
            while self.queue:
                cand = self.queue[0]
                max_new = self.engine.resolve(cand)[3]
                if max_new <= 0:               # zero budget: trivially done
                    self.queue.popleft()
                    self._live.discard(cand.uid)
                    self.completed += 1
                    events.append(StepEvent(uid=cand.uid, finished=True,
                                            finish_reason="length"))
                    continue
                if not self.backend.can_admit(len(cand.tokens)):
                    break                      # backpressure: head waits
                r = self.queue.popleft()
                break
            if r is None:
                if not self.queue:
                    continue                   # drained; try other slots
                break                          # FIFO: never admit past head
            self._admit(b, r)

    def _admit(self, b: int, r: Request) -> None:
        e = self.engine
        temp, top_k, top_p, max_new, eos, seed = e.resolve(r)
        Lp = len(r.tokens)
        self.backend.admit(b, np.asarray(r.tokens, np.int32), Lp, max_new)
        self._temp[b], self._topk[b], self._topp[b] = temp, top_k, top_p
        self._eos[b] = -1 if eos is None else eos
        self._own[b] = seed is not None
        if seed is not None:
            self.slot_keys = self.slot_keys.at[b].set(
                jax.random.PRNGKey(seed))
        self.active[b] = _Active(req=r, max_new=max_new, eos=eos)
        self._tick += 1
        self.stamp[b] = self._tick
        self.admitted += 1

    def step(self) -> List[StepEvent]:
        """Advance the core by one chunk boundary: reclaim cancelled
        requests, refill free slots from the queue, run one fused
        ``chunk``-step decode over the slot batch, and harvest the
        boundary into events.  Returns immediately (possibly with
        queued-state events only) when nothing is decodable."""
        events: List[StepEvent] = []
        self._process_cancels(events)
        self._admit_phase(events)
        if self.n_active == 0:
            return events
        self.backend.prepare_chunk(events)     # paged top-up / preemption
        if self.n_active == 0:                 # defensive; see prepare_chunk
            return events
        toks, was = self.backend.dispatch()
        self.chunks += 1
        toks_h, was_h = np.asarray(toks), np.asarray(was)
        done_h = np.asarray(self.done)
        for b in range(self.slots):
            a = self.active[b]
            if a is None:
                continue
            new = toks_h[~was_h[:, b], b]
            a.toks.extend(new.tolist())
            if done_h[b]:
                gen = np.asarray(a.toks, np.int32)
                by_eos = (a.eos is not None and gen.size > 0
                          and int(gen[-1]) == a.eos
                          and gen.size < a.max_new)
                self._finish(b, new, "eos" if by_eos else "length", events)
            elif new.size:
                events.append(StepEvent(uid=a.req.uid, new_tokens=new))
        return events

    def stats(self) -> dict:
        """Scheduler counters in the historical ``last_stats`` shape."""
        e = self.engine
        d = {
            "requests": self.completed,
            "admitted": self.admitted,
            "decode_steps": self.chunks * e.chunk,
            "scheduled_tokens": self.chunks * e.chunk * self.slots,
            "generated_tokens": self.gen_tokens,
        }
        d.update(self.backend.stats())
        return d
