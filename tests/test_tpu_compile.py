"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

Interpret mode on the CPU accepts block shapes and kernel bodies that the
TPU compiler refuses (tiling of the last two block dims, Mosaic vector
layouts), so the interpret-mode parity suites cannot catch those.  Here
each kernel is lowered and compiled for one chip of a ``v5e:2x2``
topology that is described, not attached, at the widths of the models it
serves: smollm-135m for attention and norms (3 KV heads, G=3, head_dim 64,
bf16, batch 8, S=1024, paged block 16) and mamba2-370m for the SSD
intra-chunk kernel (32 heads of 64, state 128, chunk 256).  Each test
asserts that the kernel is in the compiled program as a
``tpu_custom_call``.  One more compiles the whole decode program of the
generation engine at a small size and reads its HLO: the KV arena is
written and read in place inside the decode loop.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and pytest
workers that each import this file must all collect the same tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import flash_attention_bwd as fab
from repro.kernels import ops
from repro.kernels import paged_attention as paged
from repro.kernels import rmsnorm as rms
from repro.kernels import ssd_scan as ssd

# smollm-135m attention widths; decode reads a stacked arena of N layers
B, KV, G, D, S, N = 8, 3, 3, 64, 1024, 2
L = 512                                   # train / prefill length
BS, NB, NBLOCKS = 16, 64, 8 * 64 + 1      # paged: block, table width, pool
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(shp, dt, sharding=sharding)
            for shp, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_flash_attention_fwd_compiles(one_chip):
    _compile(lambda q, k, v: fa.flash_attention_fwd(q, k, v, q_block=256,
                                                    k_block=512),
             one_chip, ((B, KV, G, L, D), BF16), ((B, KV, L, D), BF16),
             ((B, KV, L, D), BF16))


def test_flash_attention_bwd_compiles(one_chip):
    def bwd(q, k, v, g, lse, delta):
        return fab.flash_attention_bwd(q, k, v, g, lse, delta, q_block=256,
                                       k_block=256)

    _compile(bwd, one_chip, ((B, KV, G, L, D), BF16), ((B, KV, L, D), BF16),
             ((B, KV, L, D), BF16), ((B, KV, G, L, D), BF16),
             ((B, KV, G, L), jnp.float32), ((B, KV, G, L), jnp.float32))


def test_decode_attention_compiles(one_chip):
    _compile(lambda q, k, v, n, i: dec.decode_attention_fwd(
                 q, k, v, n, i, s_block=ops.decode_block(S)),
             one_chip, ((B, KV, G, D), BF16), ((N, B, KV, S, D), BF16),
             ((N, B, KV, S, D), BF16), ((B,), jnp.int32), ((), jnp.int32))


def test_decode_attention_quant_compiles(one_chip):
    _compile(lambda q, k, v, ks, vs, n, i: dec.decode_attention_quant_fwd(
                 q, k, v, ks, vs, n, i, s_block=ops.decode_block(S)),
             one_chip, ((B, KV, G, D), BF16), ((N, B, KV, S, D), jnp.int8),
             ((N, B, KV, S, D), jnp.int8), ((N, B, KV, S), jnp.float32),
             ((N, B, KV, S), jnp.float32), ((B,), jnp.int32),
             ((), jnp.int32))


def test_paged_decode_attention_compiles(one_chip):
    _compile(paged.paged_decode_attention_fwd, one_chip,
             ((B, KV, G, D), BF16), ((N, NBLOCKS, KV, BS, D), BF16),
             ((N, NBLOCKS, KV, BS, D), BF16), ((B, NB), jnp.int32),
             ((B,), jnp.int32), ((), jnp.int32))


def test_paged_decode_attention_quant_compiles(one_chip):
    _compile(paged.paged_decode_attention_quant_fwd, one_chip,
             ((B, KV, G, D), BF16), ((N, NBLOCKS, KV, BS, D), jnp.int8),
             ((N, NBLOCKS, KV, BS, D), jnp.int8),
             ((N, NBLOCKS, KV, BS), jnp.float32),
             ((N, NBLOCKS, KV, BS), jnp.float32), ((B, NB), jnp.int32),
             ((B,), jnp.int32), ((), jnp.int32))


def test_rmsnorm_compiles(one_chip):
    _compile(lambda x, w: rms.rmsnorm_fwd(x, w, row_block=256), one_chip,
             ((B * L, 576), BF16), ((576,), jnp.float32))


def test_ssd_intra_compiles(one_chip):
    # mamba2-370m: d_inner 2048 = 32 heads x 64, state 128, chunk 256
    b, nc, q, h, p, n = 2, 2, 256, 32, 64, 128
    _compile(ssd.ssd_intra_fwd, one_chip, ((b, nc, q, h, p), BF16),
             ((b, nc, q, h), jnp.float32), ((h,), jnp.float32),
             ((b, nc, q, n), BF16), ((b, nc, q, n), BF16))


# ------------------------------------------------------------------ #
# the decode step reads and writes the KV arena where it lies
# ------------------------------------------------------------------ #
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([0-9,]*)\]\S* "
                     r"([\w-]+)\((.*)$")


def _arena_moves(text, shapes):
    """``(computation, op)`` of every copy, buffer allocation or
    dynamic-update-slice of one of ``shapes`` outside the entry
    computation: the while bodies of the decode and layer loops and what
    they call.  Moves between memory spaces (``copy-start``) are the
    compiler's prefetches and count too."""
    found, comp = [], None
    for line in text.splitlines():
        if line.startswith(("%", "ENTRY ")) and line.rstrip().endswith("{"):
            comp = None if line.startswith("ENTRY ") else line.split()[0]
            continue
        m = _HLO_OP.match(line)
        if comp is None or not m:
            continue
        shape = tuple(int(x) for x in m.group(2).split(",") if x)
        op = m.group(3)
        if op == "custom-call" and "AllocateBuffer" in m.group(4):
            op = "AllocateBuffer"
        if shape in shapes and op in ("copy", "copy-start", "AllocateBuffer",
                                      "dynamic-update-slice"):
            found.append((comp, op))
    return found


@pytest.mark.parametrize("kv_quant", [False, True])
def test_decode_step_moves_no_arena(one_chip, monkeypatch, kv_quant):
    """``gen_decode`` at a small dense config, compiled with the Pallas
    kernels: inside its loops no op copies, allocates or stacks the KV
    arena or a layer's slab (the layer scan carries the arena, the new
    row is written in place and the kernel reads the layer where it
    lies), and the arena is aliased from input to output."""
    from repro.models import transformer as T
    from repro.models.config import ModelConfig
    from repro.serving.engine import GenerationEngine

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = ModelConfig(name="t", arch_type="dense", n_layers=3, d_model=256,
                      n_heads=4, n_kv_heads=4, d_ff=512, vocab_size=512,
                      compute_dtype="bfloat16", use_pallas=True,
                      kv_quant=kv_quant)
    batch, prompt, gen = 8, 128, 128
    eng = GenerationEngine(cfg, max_new_tokens=gen)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.tree.map(on_chip, T.cache_struct(cfg, batch, prompt + gen))
    logits, key, pos, done = (
        on_chip(jax.ShapeDtypeStruct(shape, dtype)) for shape, dtype in (
            ((batch, cfg.vocab_size), jnp.float32), ((2,), jnp.uint32),
            ((batch,), jnp.int32), ((batch,), jnp.bool_)))
    compiled = eng._fixed_chunk(gen).lower(
        params, logits, cache, key, pos, done, None).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    arena = [x for seg in cache for unit in seg for n, x in unit.items()
             if n in ("k", "v")]
    shapes = {x.shape for x in arena} | {x.shape[1:] for x in arena}
    assert _arena_moves(text, shapes) == []
    arena_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    assert compiled.memory_analysis().alias_size_in_bytes >= arena_bytes


# ------------------------------------------------------------------ #
# DeepSeek-V2-Lite: the held experts' grouped matmuls and MLA's flash
# ------------------------------------------------------------------ #
def test_decode_step_slices_no_experts(one_chip, monkeypatch):
    """``gen_decode`` of one chip's share of DeepSeek-V2-Lite (a dense
    layer and 4 MoE layers holding 8 of 64 experts, published widths),
    compiled with the Pallas kernels: each layer's grouped matmuls read
    its experts in the stack over the layers, so no op inside the decode
    loops makes a buffer of one layer's experts."""
    from repro.configs.deepseek_v2_lite_16b import CONFIG
    from repro.models import transformer as T
    from repro.serving.engine import GenerationEngine

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = CONFIG.replace(n_layers=5, router_experts=64, n_experts=8,
                         vocab_size=12800, compute_dtype="bfloat16",
                         use_pallas=True)
    batch, prompt, gen = 16, 64, 64
    eng = GenerationEngine(cfg, max_new_tokens=gen)

    def on_chip(x):
        dt = BF16 if x.dtype == jnp.float32 else x.dtype
        return jax.ShapeDtypeStruct(x.shape, dt, sharding=one_chip)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0))))
    cache = jax.tree.map(on_chip, T.cache_struct(cfg, batch, prompt + gen))
    logits, key, pos, done = (
        jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        for shape, dtype in (
            ((batch, cfg.vocab_size), jnp.float32), ((2,), jnp.uint32),
            ((batch,), jnp.int32), ((batch,), jnp.bool_)))
    text = eng._fixed_chunk(gen).lower(
        params, logits, cache, key, pos, done, None).compile().as_text()
    assert "gmm" in text
    one_layer = re.compile(r"= bf16\[8,(2048,1408|1408,2048)\]")
    made = [line.strip()[:120] for line in text.splitlines()
            if one_layer.search(line) and "parameter(" not in line
            and "get-tuple-element(" not in line]
    assert made == []


@pytest.mark.parametrize("rows", [16 * 6, 16 * 512 * 6])
def test_grouped_matmul_compiles(one_chip, monkeypatch, rows):
    """The megablox kernel, forward and backward, at the held experts'
    widths (8 experts, d_model 2048, width 1408) for a decode step's and
    a training batch's worst-case rows."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def fwd_bwd(x, w, sizes):
        return jax.grad(lambda x, w: jnp.sum(ops.grouped_matmul(
            x, w, sizes).astype(jnp.float32)), argnums=(0, 1))(x, w)

    text = _compile(fwd_bwd, one_chip, ((rows, 2048), BF16),
                    ((8, 2048, 1408), BF16), ((8,), jnp.int32))
    assert "gmm" in text and "tgmm" in text


def test_flash_attention_mla_scale_compiles(one_chip, monkeypatch):
    """MLA's training attention: 16 heads of 192 (V padded to 192), YaRN's
    softmax scale, forward and backward."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)

    def fwd_bwd(q, k, v):
        return jax.grad(lambda q, k, v: jnp.sum(ops.flash_attention(
            q, k, v, sm_scale=0.11472).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    shape = ((2, L, 16, 192), BF16)
    _compile(fwd_bwd, one_chip, shape, shape, shape)
