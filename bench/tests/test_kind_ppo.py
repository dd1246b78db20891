"""The stage-3 kind's record on a tiny cell on the CPU: the program's
tracer (``repro.obs``) is on only around the traced iterations of a
``--trace 1`` run, which fill ``record["program"]``; and ``use_ema`` from
the traffic file reaches the program while the compared numbers still
pass."""
from repro import obs

from bench.lib import spec as SP
from bench.tests.tiny import run_kind, run_tiny, tiny_cell

NAME = "ppo-opt1.3b-p256g256"


def test_trace_0_never_enables_the_tracer():
    obs.reset()
    res = run_kind(tiny_cell(NAME), seed=2 ** 33 + 11, seconds=0.5, trace=0)
    assert res["record"]["program"] is None
    assert not obs._enabled
    snap = obs.snapshot()
    assert snap["spans"] == [] and snap["counters"] == {}


def test_trace_1_records_the_traced_iterations():
    cell = tiny_cell(NAME)
    tr = cell.traffic
    res = run_kind(cell, seed=2 ** 33 + 12, seconds=0.5, trace=1)
    assert not obs._enabled
    prog = res["record"]["program"]
    n = tr["trace_iterations"]
    assert prog["counters"]["gen.decode_steps"] == n * tr["gen_len"]
    assert {"ppo.generate_rollout", "gen.decode", "ppo.train_rlhf",
            "ppo.actor_step", "ema.update"} <= {sp.name for sp in
                                                prog["spans"]}
    # the spans of the traced iterations alone, each tagged with its index
    first = tr["check_steps"] + res["attempted"]
    assert {sp.step for sp in prog["spans"]} == set(range(first, first + n))
    # no TPU plane on the CPU: no program ran on a device
    assert res["record"]["trace"].modules == []


def test_use_ema_from_the_traffic(monkeypatch):
    from repro.core import ema
    calls = []
    update = ema.update
    monkeypatch.setattr(ema, "update",
                        lambda *a: calls.append(1) or update(*a))
    ppo = dict(SP.load_cell(NAME).traffic["ppo"], use_ema=False)
    line = run_tiny(tiny_cell(NAME, ppo=ppo), seed=2 ** 33 + 13)
    assert line["correct"], [c for c in line["_checks"] if not c[1] <= c[2]]
    assert calls == []
