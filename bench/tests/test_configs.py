"""A configuration of any architecture the system's ``ModelConfig``
expresses goes through the harness's loaders with no edit to a file the
benchmark has: model entries become ``ModelConfig``s field by field, the
weights and the layers' FLOPs come from the module the configuration's
``reference`` names, and the traffic's ``ppo`` block maps onto
``PPOConfig`` by name.  Unknown keys are refused by name."""
import dataclasses
import hashlib

import jax
import numpy as np
import pytest

from bench.lib import flops as F
from bench.lib import spec as SP
from bench.lib import weights as W
from bench.lib.models import model_config
from bench.tests.tiny import TINY_MODEL, tiny_cell

NAME = "ppo-opt1.3b-p256g256"
KIND = SP.load_kind("ppo_fixed")

# a tiny latent-attention, mixture-of-experts decoder
MOE = {"arch_type": "moe", "n_layers": 2, "d_model": 64, "n_heads": 4,
       "n_kv_heads": 4, "d_ff": 128, "vocab_size": 512, "mla": True,
       "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
       "v_head_dim": 16, "moe": True, "n_experts": 4, "n_shared_experts": 1,
       "top_k": 2, "moe_d_ff": 32}

# a reference module of the kind a new configuration brings; this stub
# borrows the system's initialisers for the layout, which a real
# reference may not
STUB = '''
from bench.lib.models import model_config
from repro.models import reward, transformer


def init(key, m, head):
    cfg = model_config("stub", m)
    return (transformer if head == "lm" else reward).init_params(cfg, key)


def fwd_flops(m, n_tokens, n_ctx=None):
    return 2 * n_tokens * m["d_model"] * m["n_layers"]
'''


def _with_reference(tmp_path, name, text):
    (tmp_path / "bench" / "references").mkdir(parents=True, exist_ok=True)
    (tmp_path / "bench" / "references" / f"{name}.py").write_text(text)
    return SP.load_reference(name, tmp_path)


def test_non_dense_entry_builds_its_model_config():
    from repro.models.config import ModelConfig
    cfg = model_config("m", MOE)
    for k, v in MOE.items():
        assert getattr(cfg, k) == v, k
    # what the entry leaves out takes ModelConfig's defaults
    default = ModelConfig(name="d", arch_type="moe", n_layers=1, d_model=8,
                          n_heads=1, n_kv_heads=1, d_ff=8, vocab_size=8)
    assert cfg.capacity_factor == default.capacity_factor
    assert cfg.segments()[0].unit_spec[0].moe
    assert model_config("d", TINY_MODEL).arch_type == "dense"


def test_opt_entries_build_the_same_model_configs():
    cell = SP.load_cell(NAME)
    for role, m in cell.config["models"].items():
        cfg = model_config(f"x-{role}", m)
        assert (cfg.arch_type, cfg.mla, cfg.moe) == ("dense", False, False)
        assert {k: getattr(cfg, k) for k in m} == m
        assert cfg.head_dim == 64 and cfg.logit_chunk == 512


@pytest.mark.parametrize("where", ["model", "ppo"])
def test_unknown_key_is_refused_by_name(where):
    if where == "model":
        with pytest.raises(SP.SpecError, match="n_expert_s"):
            model_config("m", dict(MOE, n_expert_s=4))
    else:
        tr = SP.load_cell(NAME).traffic
        with pytest.raises(SP.SpecError, match="ema_decy"):
            KIND.ppo_config(dict(tr, ppo=dict(tr["ppo"], ema_decy=0.9)))


def test_ppo_block_maps_onto_ppo_config_by_name():
    tr = SP.load_cell(NAME).traffic
    cfg = KIND.ppo_config(tr)
    # the stage-3 cell's settings, as the kind set them before the
    # mapping was by name
    assert dataclasses.asdict(cfg) == dict(
        max_new_tokens=256, temperature=1.0, top_k=0, top_p=1.0,
        eos_id=None, decode_chunk=32, n_samples_per_prompt=1,
        kv_layout="dense", kv_block_size=16, prefix_cache=False,
        kv_quant=False, kl_coef=0.1, clip_eps=0.2, value_clip=0.2,
        clip_reward=5.0, gamma=1.0, lam=0.95, lr_actor=1e-05,
        lr_critic=5e-06, ptx_coef=0.0, ema_decay=0.992, use_ema=True,
        is_clip=None)
    other = KIND.ppo_config(dict(tr, ppo=dict(
        tr["ppo"], use_ema=False, kv_quant=True, n_samples_per_prompt=4,
        kv_layout="paged")))
    assert (other.use_ema, other.kv_quant, other.n_samples_per_prompt,
            other.kv_layout) == (False, True, 4, "paged")
    assert set(KIND.REFERENCE_ONLY) <= set(tr["ppo"])


def test_weights_come_from_the_named_reference(tmp_path):
    stub = _with_reference(tmp_path, "stub_keys", '''
import jax.numpy as jnp


def init(key, m, head):
    return {"key": key, "w": jnp.full((m["d"],), 1.0 if head == "lm" else 2.0)}
''')
    w = W.make({"b": {"d": 3}, "a": {"d": 2}}, {"a": "lm", "b": "value"},
               2 ** 33 + 5, stub.init)
    # one key per role, split from the seed in the roles' sorted order
    keys = jax.random.split(W.seed_key(2 ** 33 + 5), 2)
    assert np.array_equal(w["a"]["key"], keys[0])
    assert np.array_equal(w["b"]["key"], keys[1])
    assert w["a"]["w"].tolist() == [1.0, 1.0]
    assert w["b"]["w"].tolist() == [2.0, 2.0, 2.0]


# sha256 (first 16 hex digits) of every leaf of the dense trees of
# tiny.TINY_MODEL for seed 2**33 + 5, recorded when ``init`` still lived
# in ``bench/lib/weights.py``
DENSE_SHA = {
    "actor/embed": "a91450834e63073f",
    "actor/final_norm": "2f20cd03c9cd392a",
    "actor/lm_head": "f5be9e35c5ba3a13",
    "actor/segments/0/0/attn/wk": "54ab2b3200b2c8bf",
    "actor/segments/0/0/attn/wo": "7696956b0f802ebd",
    "actor/segments/0/0/attn/wq": "12fbed296bacef6a",
    "actor/segments/0/0/attn/wv": "8b4877e29aadb3a8",
    "actor/segments/0/0/ln1": "02722f124d0f1736",
    "actor/segments/0/0/ln2": "02722f124d0f1736",
    "actor/segments/0/0/mlp/w_down": "61ba8f24fdf75136",
    "actor/segments/0/0/mlp/w_gate": "ecc18cf34ac671d7",
    "actor/segments/0/0/mlp/w_up": "5aab6c61105b8f9a",
    "critic/embed": "40504883caec2156",
    "critic/final_norm": "2f20cd03c9cd392a",
    "critic/segments/0/0/attn/wk": "9b1d253effa545a7",
    "critic/segments/0/0/attn/wo": "ce3c3f22e32b153c",
    "critic/segments/0/0/attn/wq": "cd78cb608d5f0018",
    "critic/segments/0/0/attn/wv": "2162cb4de09626cc",
    "critic/segments/0/0/ln1": "02722f124d0f1736",
    "critic/segments/0/0/ln2": "02722f124d0f1736",
    "critic/segments/0/0/mlp/w_down": "c75ad1ff4fa500e4",
    "critic/segments/0/0/mlp/w_gate": "c1b766d844a20644",
    "critic/segments/0/0/mlp/w_up": "7230e57bc8b54f43",
    "critic/v_head": "8b9726a62a2840f1",
}


def test_dense_trees_bit_identical():
    D = SP.load_reference("dense_decoder")
    w = W.make({"actor": TINY_MODEL, "critic": TINY_MODEL},
               KIND.HEADS, 2 ** 33 + 5, D.init)
    got = {k: hashlib.sha256(np.asarray(v).tobytes()).hexdigest()[:16]
           for k, v in W.leaves(w).items()}
    assert got == DENSE_SHA


def test_non_dense_cell_runs_through_the_kind(tmp_path):
    """A tiny MoE + MLA configuration with its own reference module, as
    new files only: its ``ModelConfig``s, weights, FLOPs and PPO settings
    reach the system, which runs a stage-3 iteration on them."""
    D = _with_reference(tmp_path, "stub_moe", STUB)
    cell = tiny_cell(NAME, gen_len=8, check_steps=1)
    cell = dataclasses.replace(
        cell, root=tmp_path,
        config=dict(cell.config, reference="stub_moe",
                    models={"actor": MOE, "critic": MOE}),
        traffic=dict(cell.traffic, ppo=dict(cell.traffic["ppo"],
                                            use_ema=False)))
    prog = KIND.Program(cell, 2 ** 33 + 7)
    assert prog.cfgs["actor"].mla and prog.cfgs["critic"].moe
    assert prog.trainer.ppo.use_ema is False and prog.trainer.ema is None
    rollout, exp, metrics, secs = prog.iteration(
        0, jax.profiler.TraceAnnotation)
    assert rollout.sequences.shape == (16, 8 + 8)
    assert all(np.isfinite(v) for v in metrics.values())
    assert all(s > 0 for s in secs)
    T = 8 + 8
    fl = F.ppo_iteration(D, MOE, MOE, 16, 8, 8)
    assert fl["generate"] == 16 * (D.fwd_flops(MOE, T - 1)
                                   + F.head_fwd(MOE, 8))
