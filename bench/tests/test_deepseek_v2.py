"""The system against the plain DeepSeek-V2 reference
(``bench/references/deepseek_v2.py``) on seeded random weights at a tiny
size with every mechanism of the configuration on: a leading dense
layer, YaRN, latent attention, the held experts' share of a routed layer
and top-k gates without renormalisation.  Float32 throughout, so the
program and the reference differ by rounding alone."""
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import flops as F
from bench.lib import spec as SP
from bench.lib import weights as W
from bench.lib.models import model_config
from bench.tests.tiny import run_tiny

CELL = "ppo-dsv2lite-ep8-p256g256"
D = SP.load_reference("deepseek_v2")
KIND = SP.load_kind("ppo_fixed")

# the cell's actor at tiny widths: 1 dense + 2 MoE layers, 4 of a 16-wide
# router's experts held, top-6, YaRN over a 64-position original window
TINY = {"n_layers": 3, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
        "d_ff": 96, "vocab_size": 97, "kv_lora_rank": 32,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_experts": 4, "router_experts": 16, "moe_d_ff": 24,
        "yarn_original_len": 64, "logit_chunk": 0,
        "compute_dtype": "float32"}
# a rounding-level gap: float32 sums in another order, logits of order 1
TOL = 2e-5


def _sizes(**over):
    m = SP.load_cell(CELL).config["models"]["actor"]
    return dict(m, **{**TINY, **over})


def _model(m, seed=3):
    cfg = model_config("tiny", dict(m, remat=False))
    w = W.make({"actor": m}, {"actor": "lm"}, seed, D.init)["actor"]
    return cfg, w


def _tokens(m, B=2, L=40, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, L), 0,
                              m["vocab_size"])


def _gap(a, b):
    return float(jnp.max(jnp.abs(a - b))) / float(jnp.max(jnp.abs(b)))


def test_tree_layout_is_the_programs():
    from repro.models import reward as R
    from repro.models import transformer as T
    m = _sizes()
    cfg, w = _model(m)
    for head, init in (("lm", T.init_params), ("value", R.init_params)):
        ref = D.init(jax.random.PRNGKey(0), m, head)
        prog = init(cfg, jax.random.PRNGKey(0))
        assert jax.tree.structure(ref) == jax.tree.structure(prog)
        assert (jax.tree.map(jnp.shape, ref)
                == jax.tree.map(jnp.shape, prog))
    # dense layer 0 at d_ff, the MoE layers' router at its full width
    assert w["segments"][0][0]["mlp"]["w_gate"].shape == (1, 64, 96)
    assert w["segments"][1][0]["mlp"]["router"].shape == (2, 64, 16)
    assert w["segments"][1][0]["mlp"]["w_gate"].shape == (2, 4, 64, 24)


def test_full_forward_logits_match_reference():
    from repro.models import transformer as T
    m = _sizes()
    cfg, w = _model(m)
    toks = _tokens(m)
    with jax.default_matmul_precision("highest"):
        h, _, _ = T.forward(cfg, w, tokens=toks)
        got = T.logits_fn(cfg, w, h)
        want = D.logits(w, D.hidden(w, toks, m))
    assert _gap(got, want) < TOL


def test_prefill_then_decode_matches_full_forward():
    """Prefill half the tokens, then decode the rest one at a time
    through the dense arena: every step's logits are the full forward's
    at that position, and the reference's."""
    from repro.models import transformer as T
    from repro.serving.generate import decode_step, prefill
    m = _sizes()
    cfg, w = _model(m)
    B, L, Lp = 2, 24, 12
    toks = _tokens(m, B, L)
    with jax.default_matmul_precision("highest"):
        h, _, _ = T.forward(cfg, w, tokens=toks)
        full = T.logits_fn(cfg, w, h)
        want = D.logits(w, D.hidden(w, toks, m))
        cache = T.init_cache(cfg, B, L)
        logits, cache = prefill(cfg, w, toks[:, :Lp], cache)
        steps = [logits]
        for t in range(Lp, L - 1):
            logits, cache = decode_step(cfg, w, toks[:, t], cache,
                                        jnp.full((B,), t, jnp.int32))
            steps.append(logits)
    dec = jnp.stack(steps, 1)                       # positions Lp-1 .. L-2
    assert _gap(dec, full[:, Lp - 1:L - 1]) < TOL
    assert _gap(dec, want[:, Lp - 1:L - 1]) < TOL


def _layer_params(w):
    """The first MoE layer's MLP, unstacked."""
    return jax.tree.map(lambda a: a[0], w["segments"][1][0]["mlp"])


def _share(p, j, n):
    """Chip ``j`` of ``n``'s share of an uncut MoE MLP: its experts (the
    first of the tree once the router's columns are rolled so that they
    come first), and the router and shared experts, which every chip
    holds."""
    k = p["w_gate"].shape[0] // n
    out = {e: p[e][j * k:(j + 1) * k] for e in ("w_gate", "w_up", "w_down")}
    out["router"] = jnp.roll(p["router"], -j * k, axis=1)
    out["shared"] = p["shared"]
    return out


@pytest.mark.parametrize("side", ["program", "reference"])
def test_shares_add_up_to_the_uncut_layer(side):
    """Each of 4 chips holds 4 of a layer's 16 experts: their partial
    outputs, with the shared experts (which every chip computes alike)
    counted once, add up to the layer with all 16 experts held."""
    from repro.models import modules as M
    n, E = 4, 16
    m = _sizes(n_experts=E, router_experts=E)
    cfg, w = _model(m)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, m["d_model"]))
    full = _layer_params(w)

    def layer(p, held):
        if side == "reference":
            return D.moe(x, p, dict(m, n_experts=held), "f32")
        return M.moe_apply(cfg.replace(n_experts=held), p, x)[0]

    with jax.default_matmul_precision("highest"):
        uncut = layer(full, E)
        no_routed = dict(full, w_down=jnp.zeros_like(full["w_down"]))
        shared = layer(no_routed, E)
        parts = [layer(_share(full, j, n), E // n) - shared
                 for j in range(n)]
    assert _gap(sum(parts) + shared, uncut) < TOL


def test_one_hot_router_drops_nothing():
    """A router that sends every token's slots to held experts, all of
    them to expert 0 first: every slot is computed (the grouped rows hold
    the worst case), as the reference computes it."""
    from repro.models import modules as M
    m = _sizes(top_k=4)
    cfg, w = _model(m)
    p = _layer_params(w)
    # expert 0 scores highest for every token, then experts 1..3: the
    # other 12 router columns score far lower
    bias = jnp.concatenate([jnp.array([40.0, 30.0, 20.0, 10.0]),
                            jnp.full((12,), -40.0)])
    x = jax.random.normal(jax.random.PRNGKey(6), (4, 32, m["d_model"]))
    p = dict(p, router=jnp.zeros_like(p["router"]).at[0].set(bias))
    x = x.at[..., 0].set(1.0)
    with jax.default_matmul_precision("highest"):
        out, _, counts = M.moe_apply(cfg, p, x)
        want = D.moe(x, p, m, "f32")
    T = 4 * 32
    assert counts.tolist() == [T * 4, 4]          # every slot held, 4 experts
    assert _gap(out, want) < TOL


def test_yarn_at_published_settings():
    """DeepSeek-V2-Lite's YaRN over its 64 rope features: the inverse
    frequencies are the extrapolated ones above the correction range
    (dims below ``low``), the interpolated ones (/ 40) below it, a linear
    ramp between; cos and sin unscaled; the softmax scale
    (1/sqrt(192)) * (0.1 * 0.707 * ln 40 + 1) ** 2."""
    m = SP.load_cell(CELL).config["models"]["actor"]
    cfg = model_config("dsv2", m)
    dim, base, f = 64, 10000.0, 40.0
    extra = base ** (-np.arange(0, dim, 2) / dim)

    def corr(rot):
        return dim * math.log(4096 / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = math.floor(corr(32)), math.ceil(corr(1))
    assert (low, high) == (10, 23)
    i = np.arange(dim // 2)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    want = extra * (1 - ramp) + extra / f * ramp
    np.testing.assert_allclose(cfg.rope_inv_freq(dim), want, rtol=1e-12)
    np.testing.assert_allclose(D.inv_freq(m, dim), want, rtol=1e-12)
    assert cfg.rope_inv_freq(dim)[0] == 1.0                 # extrapolated
    assert cfg.rope_inv_freq(dim)[-1] == pytest.approx(extra[-1] / f)
    assert cfg.rope_mscale == D.rope_scale(m) == 1.0
    scale = (0.1 * 0.707 * math.log(40) + 1) ** 2 / math.sqrt(192)
    assert cfg.softmax_scale(192) == pytest.approx(scale, rel=1e-12)
    assert D.softmax_scale(m) == pytest.approx(scale, rel=1e-12)
    assert scale == pytest.approx(0.11472, abs=5e-6)


def test_fwd_flops_count_the_held_share():
    m = SP.load_cell(CELL).config["models"]["actor"]
    one = D.fwd_flops(m, 1024)
    fewer = D.fwd_flops(dict(m, n_experts=4), 1024)
    # half the held experts: half the held slots, 1024 * 6 * 4 / 64 fewer
    assert one - fewer == 4 * 2 * (1024 * 6 * 4 // 64) * 3 * 2048 * 1408


def _tiny_cell(**traffic):
    cell = SP.load_cell(CELL)
    cfg = dict(cell.config)
    cfg["models"] = {r: dict(mm, **dict(TINY, n_layers=3 if r == "actor"
                                        else 2))
                     for r, mm in cell.config["models"].items()}
    tr = dict(cell.traffic, batch=8, prompt_len=8, gen_len=24,
              check_steps=2, trace_iterations=1, **traffic)
    return dataclasses.replace(cell, config=cfg, traffic=tr)


def test_tiny_pair_through_the_kind_is_correct():
    """The cell's traffic and limits on a tiny DeepSeek-V2 pair: the
    program through ``ppo_fixed.Program`` and the reference agree, and
    the iteration reaches the tracer's MoE counters."""
    cell = _tiny_cell()
    line = run_tiny(cell, seed=2 ** 33 + 7, seconds=0.5)
    assert line["correct"], json.dumps(line["checks"])
    assert line["checks"]["logprob_rms_gap"]["value"] < 1e-4
    prog = KIND.Program(cell, 2 ** 33 + 9)
    assert prog.trainer.ppo.use_ema is False
    assert prog.cfgs["actor"].n_dense_layers == 1
    from repro import obs
    obs.reset()
    obs.enable()
    try:
        prog.iteration(0, jax.profiler.TraceAnnotation)
    finally:
        obs.disable()
    c = obs.snapshot()["counters"]
    B, L, K = 8, 8 + 24, 6
    assert c["moe.routed_slots.gen.actor"] == B * L * K * 2
    for role in ("actor", "ref", "critic", "reward"):
        assert c[f"moe.routed_slots.score.{role}"] == (
            B * L * K * (2 if role in ("actor", "ref") else 1))
    for key, v in c.items():
        if key.startswith("moe.held_slots."):
            routed = c[key.replace("held_slots", "routed_slots")]
            assert 0 < v <= routed
    assert c["moe.layers_held"] == 2
    fl = F.ppo_iteration(D, cell.config["models"]["actor"],
                         cell.config["models"]["critic"], B, 8, 24)
    assert fl["total"] > 0


def test_lean_reference_reads_as_ppo_fixed_s():
    """The cell's kind, ``ppo_fixed_lean``, computes ``ppo_fixed``'s
    reference with less held at once: on a tiny pair its readings are
    bitwise ``ppo_fixed``'s, and its ``run`` and ``calibrate`` read its
    own reference."""
    cell = _tiny_cell()
    lean = SP.load_kind("ppo_fixed_lean")
    assert cell.kind == "ppo_fixed_lean"
    for fn in (lean.run, lean.calibrate):
        assert fn.__globals__["reference_record"] is lean.reference_record
    seed = 2 ** 33 + 11
    prog = KIND.Program(cell, seed)
    rec = KIND.check_steps(prog, jax.profiler.TraceAnnotation,
                           cell.traffic["check_steps"])
    del prog
    a = lean.reference_record(cell, seed, rec)
    b = KIND.reference_record(cell, seed, rec)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_moe_experts_roofline_reader():
    """The reader's least time comes from the counters, its busy time
    from the grouped-matmul ops and the data moves into their operands
    within the same program run (a slice of the stacked experts, the
    copy behind a copy-done), as the union of their intervals; not from
    an operand that computes, nor from an op of another program that
    bears an operand's name.  Nothing to read without counters (a
    program that has no such counters, or a trace without peaks)."""
    from bench.lib.device import peaks_for
    from bench.lib.trace import Op
    read = SP.load_metric_reader("kernel.moe_experts.roofline")
    expert_work = read.__globals__["expert_work"]
    least_by_phase = read.__globals__["least_by_phase"]
    cell = SP.load_cell(CELL)
    peaks = peaks_for("TPU v5 lite")
    w = "bf16[8,2048,1408]{2,1,0:T(8,128)(2,1)S(1)}"
    ops = [
        # program "step" runs from 0 to 10 ms
        Op("ds", f"%dynamic-slice_bitcast_fusion.14 = {w} fusion(bf16[4,8,"
           "2048,1408] %get-tuple-element.3, s32[] %get-tuple-element.4), "
           "kind=kLoop, calls=%fused_computation.23", 0.0, 1e-3),
        Op("cs", "%copy-start.2 = (bf16[96,2048], bf16[96,2048], u32[]) "
           "copy-start(bf16[96,2048] %pad.7)", 1e-3, 3e-3),
        Op("cd", "%copy-done.2 = bf16[96,2048] copy-done((bf16[96,2048], "
           "bf16[96,2048], u32[]) %copy-start.2)", 3e-3, 3.5e-3),
        Op("gmm.3", f"%gmm.3 = bf16[128,1408] custom-call(s32[9] "
           f"%pad_add_fusion.8, bf16[96,2048] %copy-done.2, {w} "
           "%dynamic-slice_bitcast_fusion.14), custom_call_target="
           '"tpu_custom_call"', 2e-3, 4e-3),
        Op("tgmm.1", "%tgmm.1 = bf16[8,2048,1408] custom-call(bf16[96,2048] "
           "%fusion.9), custom_call_target=\"tpu_custom_call\"", 4e-3,
           7e-3),
        Op("fusion.9", "%fusion.9 = bf16[96,2048] fusion(...), kind=kLoop",
           7e-3, 9e-3),
        Op("add", "%pad_add_fusion.8 = s32[9] fusion(...), kind=kLoop",
           9e-3, 9.5e-3),
        # program "other", 10 to 20 ms: an op of the same name
        Op("ds2", f"%dynamic-slice_bitcast_fusion.14 = {w} fusion(...), "
           "kind=kLoop", 11e-3, 15e-3),
    ]
    modules = [("step", 0.0, 10e-3), ("other", 10e-3, 20e-3)]
    counters = {"moe.held_slots.train.actor": 6144.0,
                "moe.active_experts.train.actor": 32.0,
                "moe.held_slots.gen.actor": 12.0,
                "moe.active_experts.gen.actor": 25.0,
                "gen.decode_steps": 1.0}
    trace = dataclasses.make_dataclass("T", ["device_ops", "modules"])(
        ops, modules)
    rec = {"trace": trace, "program": {"counters": counters}, "cell": cell,
           "peaks": peaks}
    fl, by = expert_work(6144, 32, 2048, 1408)
    assert fl == 6144 * 3 * 2 * 2048 * 1408
    assert by == 2 * (32 * 3 * 2048 * 1408 + 6144 * 3 * (2048 + 1408))
    gf, gb = expert_work(12, 25, 2048, 1408)
    phases = least_by_phase(rec)
    assert phases["train"] == max(3 * fl / peaks.bf16_flops,
                                  3 * by / peaks.hbm_bytes_per_s)
    assert phases["gen"] == max(gf / peaks.bf16_flops,
                                gb / peaks.hbm_bytes_per_s)
    # the slice 0-1 ms, the copy 1-3.5 ms under the kernel 2-4 ms, tgmm
    # 4-7 ms: 7 ms, none of it twice
    assert read(rec) == pytest.approx(100 * sum(phases.values()) / 7e-3)
    assert read(dict(rec, program={"counters": {}})) is None
    assert read(dict(rec, peaks=None)) is None
