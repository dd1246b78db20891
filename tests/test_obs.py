"""The in-program tracer (``repro.obs``): spans, counters and iteration
tags; the timers folded into it keep filling the trainer's and pipeline's
fields; every stage-3 program compiles under a name of its own."""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import (HybridEngine, PPOConfig, RLHFEngine, RLHFPipeline,
                        StageConfig)
from repro.core import ema as EMA
from repro.core.ppo import PPOTrainer
from repro.core.replay import WeightPublisher
from repro.data import ConstantTaskDataset, CopyTaskDataset, DataBlender
from repro.launch.mesh import make_local_mesh
from repro.models import reward as R
from repro.models import transformer as T
from repro.models.config import ModelConfig

V = 64
ACTOR = ModelConfig(name="a", arch_type="dense", n_layers=1, d_model=32,
                    n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=V,
                    compute_dtype="float32", remat=False)
CRITIC = ACTOR.replace(name="c")
GEN = 4
# what docs/scaling.md documents of HybridEngine.last_reshard_stats
RESHARD_KEYS = {"direction", "seconds", "first_call", "in_bytes",
                "out_bytes", "gathered_bytes", "freed_bytes"}


@pytest.fixture(autouse=True)
def tracer_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


class FakeAnnotation:
    opened = []

    def __init__(self, name):
        FakeAnnotation.opened.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def annotations(monkeypatch):
    FakeAnnotation.opened = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    return FakeAnnotation.opened


# ----------------------------- the tracer ----------------------------- #
def test_disabled_span_records_nothing_and_opens_no_annotation(
        annotations):
    with obs.span("a.outer") as sp:
        with obs.span("a.inner", k=1):
            obs.count("c")
    assert sp.seconds >= 0.0
    assert annotations == []
    assert obs.snapshot() == {"spans": [], "counters": {}}


def test_span_seconds_measure_open_and_closed_spans():
    with obs.span("a.x") as sp:
        first = sp.seconds
        second = sp.seconds
    closed = sp.seconds
    assert 0.0 <= first <= second <= closed
    assert sp.seconds == closed


def test_nesting_parent_step_and_annotations(annotations):
    obs.enable()
    obs.step(7)
    with obs.span("ppo.outer"):
        with obs.span("gen.inner", rows=4):
            pass
        obs.step(8)
        with obs.span("gen.other"):
            pass
    spans = {s.name: s for s in obs.snapshot()["spans"]}
    assert [s.name for s in obs.snapshot()["spans"]] == [
        "gen.inner", "gen.other", "ppo.outer"]
    assert spans["ppo.outer"].parent is None
    assert spans["gen.inner"].parent == "ppo.outer"
    assert spans["gen.other"].parent == "ppo.outer"
    assert spans["gen.inner"].attrs == {"rows": 4}
    assert spans["gen.inner"].step == 7
    assert spans["gen.other"].step == 8
    o, i = spans["ppo.outer"], spans["gen.inner"]
    assert o.start_ns <= i.start_ns <= i.end_ns <= o.end_ns
    assert annotations == ["ppo.outer", "gen.inner", "gen.other"]


def test_parents_are_per_thread():
    obs.enable()
    seen = {}

    def worker():
        with obs.span("w.root"):
            pass
        seen["done"] = True

    with obs.span("main.root"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    spans = {s.name: s for s in obs.snapshot()["spans"]}
    assert seen["done"]
    assert spans["w.root"].parent is None
    assert spans["main.root"].parent is None


def test_steps_are_per_thread():
    obs.enable()
    obs.step(4)

    def producer():
        obs.step(5)
        with obs.span("gen.decode"):
            pass

    t = threading.Thread(target=producer)
    t.start()
    t.join()
    with obs.span("ppo.train_rlhf"):
        pass
    spans = {s.name: s for s in obs.snapshot()["spans"]}
    assert spans["gen.decode"].step == 5
    assert spans["ppo.train_rlhf"].step == 4


def test_ring_is_bounded():
    obs.enable()
    for i in range(obs.RING + 10):
        with obs.span(f"s.{i}"):
            pass
    spans = obs.snapshot()["spans"]
    assert len(spans) == obs.RING
    assert spans[0].name == "s.10"
    assert spans[-1].name == f"s.{obs.RING + 9}"


def test_counters_enable_disable_reset():
    obs.count("n", 5)
    obs.enable()
    obs.count("n", 2)
    obs.count("n")
    obs.count("m", 0.5)
    assert obs.snapshot()["counters"] == {"n": 3, "m": 0.5}
    obs.disable()
    obs.count("n", 100)
    assert obs.snapshot()["counters"]["n"] == 3
    obs.reset()
    assert obs.snapshot() == {"spans": [], "counters": {}}


def test_named_sets_the_program_name_and_keeps_donation():
    f = jax.jit(obs.named("my_prog", lambda a, b: a + b),
                donate_argnums=(0,))
    text = f.lower(jnp.ones(3), jnp.ones(3)).as_text()
    assert "module @jit_my_prog" in text
    assert "jax.buffer_donor = true" in text or "tf.aliasing_output" in text
    np.testing.assert_allclose(f(jnp.ones(3), jnp.ones(3)), 2.0)


# --------------------------- folded timers ---------------------------- #
def make_trainer():
    he = HybridEngine(ACTOR, make_local_mesh())
    pa = T.init_params(ACTOR, jax.random.PRNGKey(1))
    pc = R.init_params(CRITIC, jax.random.PRNGKey(2))
    return PPOTrainer(actor_cfg=ACTOR, critic_cfg=CRITIC, actor_params=pa,
                      critic_params=pc, ref_params=pa, reward_params=pc,
                      ppo=PPOConfig(max_new_tokens=GEN), engine=he)


@pytest.fixture(scope="module")
def trainer():
    return make_trainer()


PROMPTS = jnp.asarray(np.arange(2 * 6).reshape(2, 6) % V, jnp.int32)


@pytest.mark.parametrize("on", [False, True])
def test_trainer_fields_from_spans(trainer, on):
    if on:
        obs.enable()
        obs.step(3)
    rollout, gm = trainer.generate_rollout(PROMPTS, jax.random.PRNGKey(0))
    exp, sm = trainer.score_rollout(rollout)
    tm = trainer.train_rlhf(exp)
    assert gm["gen_tok_s"] > 0 and np.isfinite(gm["gen_tok_s"])
    assert gm["gen_len"] == GEN
    assert gm["decode_steps"] == GEN
    assert gm["reshard_s"] > 0
    assert gm["reshard_bytes"] == 0.0          # one device: nothing gathered
    rs = trainer.engine.last_reshard_stats
    assert set(rs) == RESHARD_KEYS
    assert rs["direction"] == "to_inference" and rs["seconds"] > 0
    assert rs["in_bytes"] == rs["out_bytes"] > 0
    assert np.isfinite(sm["reward_score"]) and np.isfinite(tm["v_loss"])
    snap = obs.snapshot()
    if not on:
        assert snap == {"spans": [], "counters": {}}
        return
    parent = {s.name: s.parent for s in snap["spans"]}
    assert parent["ppo.generate_rollout"] is None
    for child in ("hybrid.to_inference", "gen.prefill", "gen.decode",
                  "gen.assemble"):
        assert parent[child] == "ppo.generate_rollout"
    for child in ("ppo.make_experience", "ppo.score_read"):
        assert parent[child] == "ppo.score_rollout"
    for child in ("ppo.actor_step", "ppo.critic_step", "ema.update",
                  "ppo.train_read"):
        assert parent[child] == "ppo.train_rlhf"
    assert {s.step for s in snap["spans"]} == {3}
    # one decode dispatch; the actor's one attention layer is read in place
    assert snap["counters"] == {"gen.decode_steps": GEN,
                                "gen.decode.inplace_layers": 1,
                                "gen.decode.copied_layers": 0}
    reshard = [s for s in snap["spans"] if s.name == "hybrid.to_inference"]
    assert rs["seconds"] == pytest.approx(
        (reshard[-1].end_ns - reshard[-1].start_ns) * 1e-9)


@pytest.mark.parametrize("arch,extra,split", [
    # every layer an attention arena: all read and written in place
    ("dense", {"n_layers": 3}, (3, 0)),
    # ssm, attn units: the SSM state goes through a slice of the stack
    ("hybrid", {"n_layers": 4, "attn_every": 2, "ssm_state": 16,
                "ssm_headdim": 16, "ssm_chunk": 4}, (2, 2)),
])
def test_decode_layer_counters(arch, extra, split):
    """``gen.decode.inplace_layers`` / ``copied_layers`` count, on each
    ``gen_decode`` dispatch, the layers whose cache the step reads where
    it lies and those it slices out and writes back."""
    from repro.serving.engine import GenerationEngine
    cfg = ACTOR.replace(name=arch, arch_type=arch, **extra)
    eng = GenerationEngine(cfg, max_new_tokens=GEN, eos_id=V - 1, chunk=2)
    obs.enable()
    eng.generate(T.init_params(cfg, jax.random.PRNGKey(0)), PROMPTS,
                 jax.random.PRNGKey(1))
    n = obs.snapshot()["counters"]
    dispatches = eng.last_stats["decode_steps"] // 2
    assert dispatches >= 1
    assert (n["gen.decode.inplace_layers"],
            n["gen.decode.copied_layers"]) == tuple(
                dispatches * x for x in split)


@pytest.mark.parametrize("on", [False, True])
def test_publish_stats_from_span(on):
    if on:
        obs.enable()
    pub = WeightPublisher()
    st = pub.publish({"w": jnp.ones(4)}, 1)
    assert st["seconds"] > 0 and st["bytes"] == 0 and st["version"] == 1
    assert pub.total_publish_s == st["seconds"]
    names = [s.name for s in obs.snapshot()["spans"]]
    assert names == (["replay.publish"] if on else [])


@pytest.mark.parametrize("on", [False, True])
def test_pipeline_timings_from_spans(on):
    if on:
        obs.enable()
    ds = [ConstantTaskDataset(100, 6, 6, V, seed=1),
          CopyTaskDataset(100, 6, 6, V, seed=2)]
    pipe = RLHFPipeline(
        RLHFEngine(ACTOR, CRITIC, jax.random.PRNGKey(0),
                   mesh=make_local_mesh()),
        DataBlender(ds, [0.5, 0.5], seed=0),
        StageConfig(sft_steps=2, sft_batch=4, rm_steps=2, rm_batch=4,
                    ppo_steps=2, ppo_batch=4),
        PPOConfig(max_new_tokens=GEN))
    out = pipe.run()
    t = out["timings"]
    assert set(t) == {"stage1", "stage2", "stage3"}
    assert all(v > 0 for v in t.values())
    assert pipe.gen_tok_s > 0
    spans = obs.snapshot()["spans"]
    if not on:
        assert spans == []
        return
    stages = {s.name: s for s in spans if s.name.startswith("pipeline.")}
    for k in t:
        s = stages[f"pipeline.{k}"]
        assert t[k] == pytest.approx((s.end_ns - s.start_ns) * 1e-9)
    roots = [s for s in spans if s.name == "ppo.generate_rollout"]
    assert [s.step for s in roots] == [0, 1]
    assert {s.parent for s in roots} == {"pipeline.stage3"}


# ------------------------- named programs ----------------------------- #
def _lowered(trainer, name):
    """The program ``name`` of the stage-3 path, lowered at a tiny size."""
    he, eng = trainer.engine, trainer.gen_engine
    pa = trainer.actor.params
    logits, cache, _ = jax.eval_shape(eng._prefill_fixed, pa, PROMPTS, None)
    seqs = jax.ShapeDtypeStruct((2, 6 + GEN), jnp.int32)
    mask = jax.ShapeDtypeStruct((2, 6 + GEN), jnp.bool_)
    exp_args = (pa, trainer.ref_params, trainer.critic.params,
                trainer.reward_params, seqs, mask)
    exp, _, _ = jax.eval_shape(trainer._mk_exp, *exp_args)
    B = jax.ShapeDtypeStruct((2,), jnp.int32)
    done = jax.ShapeDtypeStruct((2,), jnp.bool_)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return {
        "gen_prefill": lambda: eng._prefill_fixed.lower(pa, PROMPTS, None),
        "gen_decode": lambda: eng._fixed_chunk(GEN).lower(
            pa, logits, cache, key, B, done, None),
        "hybrid_to_inference": lambda: he._to_infer.lower(pa),
        "hybrid_to_train": lambda: he._to_train.lower(pa),
        "ppo_make_experience": lambda: trainer._mk_exp.lower(*exp_args),
        "ppo_actor_step": lambda: trainer._actor_step.lower(
            trainer.actor, exp, None),
        "ppo_critic_step": lambda: trainer._critic_step.lower(
            trainer.critic, exp),
        "ema_update": lambda: EMA.update.lower(trainer.ema, pa, 0.992),
    }[name]()


@pytest.mark.parametrize("name", [
    "gen_prefill", "gen_decode", "hybrid_to_inference", "hybrid_to_train",
    "ppo_make_experience", "ppo_actor_step", "ppo_critic_step",
    "ema_update"])
def test_stage3_program_names(trainer, name):
    assert f"module @jit_{name} " in _lowered(trainer, name).as_text()
