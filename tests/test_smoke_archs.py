"""Per-assigned-architecture smoke tests: a REDUCED variant of the same
family (<=2 layers, d_model<=512, <=4 experts — hybrid/vlm keep one full
interleave unit) runs one forward and one train step on CPU, asserting
output shapes and finiteness; decode-capable archs also run one serve
step against a KV cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.models import transformer as T
from repro.serving.generate import decode_step, prefill
from repro.training.steps import lm_train_step
from repro.training.train_state import TrainState

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B, L, key):
    batch = {}
    if cfg.embed_inputs:
        batch["tokens"] = jax.random.randint(key, (B, L), 0, cfg.vocab_size)
    else:
        batch["embeds"] = jax.random.normal(key, (B, L, cfg.d_model),
                                            jnp.float32) * 0.02
    batch["labels"] = jax.random.randint(key, (B, L), 0, cfg.vocab_size)
    batch["mask"] = jnp.ones((B, L), jnp.float32)
    if cfg.arch_type == "vlm":
        batch["encoder_embeds"] = jax.random.normal(
            key, (B, cfg.encoder_len, cfg.encoder_dim)) * 0.02
    return batch


@pytest.mark.parametrize("arch", list(ARCHS))
def test_reduced_smoke(arch):
    cfg = reduced(ARCHS[arch])
    B, L = 2, 16
    params = T.init_params(cfg, KEY)
    batch = _batch(cfg, B, L, KEY)

    # forward
    h, _, aux = T.forward(cfg, params,
                          tokens=batch.get("tokens"),
                          embeds=batch.get("embeds"),
                          encoder_embeds=batch.get("encoder_embeds"),
                          mode="full")
    assert h.shape == (B, L, cfg.d_model)
    assert np.isfinite(np.asarray(h, np.float32)).all()
    logits = T.logits_fn(cfg, params, h[:, -1:])
    assert logits.shape == (B, 1, cfg.vocab_size)

    # one train step: loss finite, params change
    state = TrainState.create(params)
    state2, m = jax.jit(lambda s, b: lm_train_step(cfg, s, b, 1e-3))(
        state, batch)
    assert np.isfinite(float(m["loss"]))
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree.leaves(state.params), jax.tree.leaves(state2.params)))
    assert delta > 0

    # one decode step (all assigned archs are decoder-style)
    cache = T.init_cache(cfg, B, L + 4)
    _, cache = prefill(cfg, params, batch.get("tokens"), cache,
                       embeds=batch.get("embeds"),
                       encoder_embeds=batch.get("encoder_embeds"))
    tok = jnp.zeros((B,), jnp.int32)
    emb = (None if cfg.embed_inputs
           else jnp.zeros((B, 1, cfg.d_model), jnp.float32))
    lg, cache = decode_step(cfg, params, tok, cache,
                            jnp.full((B,), L, jnp.int32), embeds=emb)
    assert lg.shape == (B, cfg.vocab_size)
    assert np.isfinite(np.asarray(lg, np.float32)).all()


@pytest.mark.parametrize("arch", list(ARCHS))
def test_full_config_spec_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = ARCHS[arch]
    expect = {
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "musicgen-medium": (48, 1536, 24, 24, 6144, 2048),
        "yi-9b": (48, 4096, 32, 4, 11008, 64000),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "mamba2-370m": (48, 1024, 0, 0, 0, 50280),
        "zamba2-1.2b": (38, 2048, 32, 32, 8192, 32000),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 10944, 102400),
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "llama-3.2-vision-11b": (40, 4096, 32, 8, 14336, 128256),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab_size)
    assert got == expect, (arch, got, expect)


def test_arch_special_features():
    assert ARCHS["qwen3-8b"].qk_norm
    assert ARCHS["deepseek-v2-lite-16b"].mla
    assert ARCHS["deepseek-v2-lite-16b"].kv_lora_rank == 512
    assert ARCHS["deepseek-v2-lite-16b"].n_experts == 64
    assert ARCHS["deepseek-v2-lite-16b"].top_k == 6
    assert ARCHS["llama4-scout-17b-a16e"].n_experts == 16
    assert ARCHS["llama4-scout-17b-a16e"].top_k == 1
    assert ARCHS["mamba2-370m"].ssm_state == 128
    assert ARCHS["zamba2-1.2b"].ssm_state == 64
    assert not ARCHS["musicgen-medium"].embed_inputs
    assert ARCHS["llama-3.2-vision-11b"].cross_attn_every == 5
    # layer accounting
    for a, cfg in ARCHS.items():
        assert sum(s.n_layers for s in cfg.segments()) == cfg.n_layers, a
