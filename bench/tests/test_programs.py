"""Device time per named program, idle time by program span and the
stall finder on a constructed trace; the two per-layer readers of the
program's programs and counters; the stage-3 split tool end to end on a
tiny cell on the CPU."""
import dataclasses

import pytest

from bench.lib import programs as PG
from bench.lib import trace as TR
from bench.tests.test_trace import Ev
from repro.obs import Span

MS = 1e6


def planes(iterations=2, window_ms=None):
    """Two iterations of 100 ms: to_inference 0-2, prefill 3-10, decode
    10-70 (60 ms, 4 steps of 15), scoring 72-80, actor 80-92, critic
    92-96, EMA 96-98 (ms from the iteration's start).  The traced window
    spans the iterations unless ``window_ms`` cuts it short."""
    host = [Ev(TR.WINDOW_SPAN, 0, (window_ms or 100 * iterations) * MS)]
    mods, ops = [], []
    prog = [("jit_hybrid_to_inference(11)", 0, 2),
            ("jit_gen_prefill(12)", 3, 10), ("jit_gen_decode(13)", 10, 70),
            ("jit_ppo_make_experience(14)", 72, 80),
            ("jit_ppo_actor_step(15)", 80, 92),
            ("jit_ppo_critic_step(16)", 92, 96),
            ("jit_ema_update(17)", 96, 98)]
    spans = [("ppo.generate_rollout", 0, 71), ("hybrid.to_inference", 0, 2.5),
             ("gen.prefill", 2.5, 3.5), ("gen.decode", 3.5, 70.5),
             ("gen.assemble", 70.5, 71), ("stage3.score", 71, 80),
             ("ppo.score_rollout", 71, 80), ("ppo.train_rlhf", 80, 99.5),
             ("ppo.train_read", 98, 99.5),
             ("$ppo.py:500 train_rlhf", 80, 99.5)]
    for k in range(iterations):
        t = 100 * k
        for name, a, b in prog:
            mods.append(Ev(name, (t + a) * MS, (b - a) * MS))
            ops.append(Ev("%fusion", (t + a) * MS, (b - a) * MS))
        for name, a, b in spans:
            host.append(Ev(name, (t + a) * MS, (b - a) * MS))
    return [("/host:CPU", [("python", host)]),
            ("/device:TPU:0", [("XLA Modules", mods), ("XLA Ops", ops)])]


def test_program_names_strip_jit_and_hash():
    assert PG.program_name("jit_gen_decode(123)") == "gen_decode"
    assert PG.program_name("jit__lambda(9)") == "_lambda"
    assert PG.program_name("copy") == "copy"


def test_module_seconds_per_program_and_iteration():
    mods = TR.reduce_events(planes()).modules
    by = PG.seconds_by_program(mods)
    assert by["gen_decode"] == (2, pytest.approx(0.12))
    it = {n: PG.per_iteration(mods, n, 2) for n in by}
    assert it["gen_decode"] == pytest.approx(0.060)
    assert it["hybrid_to_inference"] == pytest.approx(0.002)
    assert it["ppo_make_experience"] == pytest.approx(0.008)
    assert it["ppo_actor_step"] == pytest.approx(0.012)
    assert it["ppo_critic_step"] == pytest.approx(0.004)
    assert it["ema_update"] == pytest.approx(0.002)
    # 120 ms of decode over 8 counted steps
    assert PG.decode_step_ms(mods, {"gen.decode_steps": 8}) == (
        pytest.approx(15.0))
    assert PG.decode_step_ms(mods, {"gen.decode_steps": 0}) is None
    assert PG.decode_step_ms(mods, {}) is None


def test_per_iteration_is_none_unless_once_per_iteration():
    mods = TR.reduce_events(planes()).modules
    assert PG.per_iteration(mods, "gen_decode", 3) is None
    assert PG.per_iteration(mods, "no_such_program", 2) is None
    # a window that cuts the second iteration short clips its programs
    cut = TR.reduce_events(planes(window_ms=150)).modules
    assert PG.seconds_by_program(cut)["gen_decode"] == (
        2, pytest.approx(0.060 + 0.040))


def test_modules_only_from_tpu_planes():
    p = planes()
    p.append(("/device:TPU:0 SparseCore 0",
              [("XLA Modules", [Ev("jit_gen_decode(13)", 0, 5 * MS)])]))
    mods = TR.reduce_events(p).modules
    assert PG.seconds_by_program(mods)["gen_decode"][0] == 2


def test_idle_by_span_puts_idle_in_innermost_program_span():
    s = TR.reduce_events(planes())
    idle = dict(s.idle_by_span)
    # per iteration, each hole to the shortest program span over its
    # midpoint: 2-3 ms to gen.prefill (2.5 is also hybrid.to_inference's
    # end), 70-72 ms to gen.assemble, 98-100 ms to ppo.train_read (the
    # Python frame over it is no program span)
    assert idle["gen.prefill"] == pytest.approx(0.002)
    assert idle["gen.assemble"] == pytest.approx(0.004)
    assert idle["ppo.train_read"] == pytest.approx(0.004)
    assert sum(idle.values()) == pytest.approx(0.2 - s.busy_s)
    assert TR.OUTSIDE not in idle


def test_idle_outside_spans_and_existing_gaps_unchanged():
    p = planes(iterations=1)
    host = p[0][1][0][1]
    p[0] = ("/host:CPU", [("python", [
        e for e in host if e.name not in ("ppo.train_read",
                                          "ppo.train_rlhf")])])
    s = TR.reduce_events(p)
    idle = dict(s.idle_by_span)
    # 98-100 ms: only a Python frame covers it, which is no program span
    assert idle[TR.OUTSIDE] == pytest.approx(0.002)
    # the harness's own reduction is untouched by the module line and the
    # program spans: idle_gaps still label by the innermost host event
    gaps = dict(s.idle_gaps)
    assert gaps["$ppo.py:500 train_rlhf"] == pytest.approx(0.002)
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    bd = TR.breakdown(s)
    assert set(bd) == {"device_ops", "idle_gaps", "idle_by_span"}
    assert bd["idle_gaps"] == [[n, t] for n, t in s.idle_gaps]
    assert bd["idle_by_span"] == [[n, t] for n, t in s.idle_by_span]


def _sp(name, a, b, parent=None, step=0):
    return Span(name, int(a * MS), int(b * MS), parent, step, {})


def test_stall_finds_the_root_and_child_that_stalled():
    spans = []
    for k in range(5):
        t = 100 * k
        extra = 40 if k == 3 else 0
        spans += [_sp("gen.decode", t, t + 60 + extra,
                      "ppo.generate_rollout", k),
                  _sp("gen.prefill", t + 60 + extra, t + 70 + extra,
                      "ppo.generate_rollout", k),
                  _sp("ppo.generate_rollout", t, t + 70 + extra, None, k),
                  _sp("ppo.train_rlhf", t + 70 + extra, t + 95 + extra,
                      None, k)]
    st = PG.stall(spans)
    assert st["span"] == "ppo.generate_rollout" and st["step"] == 3
    assert st["excess_s"] == pytest.approx(0.040)
    assert st["child"] == "gen.decode"
    assert st["child_excess_s"] == pytest.approx(0.040)
    assert PG.stall([]) is None


def test_stage3_split_tool_on_a_tiny_cell():
    import jax
    from bench.tests.tiny import tiny_cell
    from bench.tools import stage3_split as S
    cell = tiny_cell("ppo-opt1.3b-p256g256")
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic, gen_len=8,
                                                  check_steps=1))
    out = S.split(cell, 2 ** 33 + 5, 0.2, 1, jax)
    assert [w["tracer"] for w in out["windows"]] == [False, True]
    assert all(w["ppo_iter_s"] > 0 and isinstance(w["slow"], list)
               for w in out["windows"])
    assert out["stall"]["span"].startswith("ppo.")
    assert {"ppo.generate_rollout", "gen.decode", "ppo.train_read"} <= set(
        out["host_span_median_s"])
    tr = out["trace"]
    assert tr["decode_steps"] == 8 * cell.traffic["trace_iterations"]
    # no TPU plane on the CPU: nothing to read per program
    assert tr["per_iteration_s"]["gen_decode"] is None
    assert tr["decode_step_ms"] is None
    assert tr["window_s"] > 0


def _record(**over):
    rec = {"trace": TR.reduce_events(planes()), "trace_iterations": 2,
           "program": {"spans": [], "counters": {"gen.decode_steps": 8}}}
    return dict(rec, **over)


def test_decode_step_reader():
    from bench.lib import spec as SP
    read = SP.load_metric_reader("stage3.decode_step_ms")
    # 2 x 60 ms of gen_decode over 8 counted steps
    assert read(_record()) == pytest.approx(15.0)
    assert read(_record(trace=None)) is None
    assert read(_record(program=None)) is None
    assert read(_record(program={"spans": [], "counters": {}})) is None
    no_tpu = [pl for pl in planes() if pl[0].startswith("/host:")]
    assert read(_record(trace=TR.reduce_events(no_tpu))) is None


def test_actor_step_reader():
    from bench.lib import spec as SP
    read = SP.load_metric_reader("stage3.actor_step_s")
    # 12 ms of ppo_actor_step in each of the 2 traced iterations
    assert read(_record()) == pytest.approx(0.012)
    assert read(_record(trace=None)) is None
    assert read(_record(trace_iterations=3)) is None
    no_tpu = [pl for pl in planes() if pl[0].startswith("/host:")]
    assert read(_record(trace=TR.reduce_events(no_tpu))) is None
