"""Stage-3 cell at a size the CPU holds: a sound run is correct; a run
with the timed path broken underneath is not, once for each fault the
cell can have (a step that returns its state unchanged; half of the
batch left out, the mean taken over the rest; a token altered where it
is sampled); and the control, the reference in float8 in the program's
place, fails the cell's limits.  The cell runs on one chip, so there is
no exchange between chips to leave out."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import compare as C
from bench.lib import spec as SP
from bench.tests.tiny import run_tiny, tiny_cell

NAME = "ppo-opt1.3b-p256g256"


def failing(line):
    return {n for n, v, lim in line["_checks"] if not v <= lim}


def test_sound_run_is_correct():
    line = run_tiny(tiny_cell(NAME), seed=5)
    assert line["correct"], failing(line)


def _unchanged(step):
    def f(cfg, ppo, state, exp, *a):
        _, metrics = step(cfg, ppo, state, exp, *a)
        return state, metrics
    return f


def _half_batch(step):
    def f(cfg, ppo, state, exp, *a):
        half = type(exp)(*(x[: x.shape[0] // 2] for x in exp))
        return step(cfg, ppo, state, half, *a)
    return f


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_token"])
def test_fault_makes_run_incorrect(fault, monkeypatch):
    from repro.core import ppo
    from repro.serving import engine
    if fault == "altered_token":
        gen = engine.GenerationEngine.generate

        def altered(self, params, tokens, key, **kw):
            out = gen(self, params, tokens, key, **kw)
            seq = jnp.where(out["response_mask"],
                            (out["sequences"] + 1) % self.cfg.vocab_size,
                            out["sequences"])
            return dict(out, sequences=seq)
        monkeypatch.setattr(engine.GenerationEngine, "generate", altered)
    else:
        wrap = _unchanged if fault == "unchanged_state" else _half_batch
        monkeypatch.setattr(ppo, "actor_step", wrap(ppo.actor_step))
        monkeypatch.setattr(ppo, "critic_step", wrap(ppo.critic_step))
    line = run_tiny(tiny_cell(NAME), seed=5)
    assert not line["correct"]
    want = {"unchanged_state": "param_change_gap",
            "half_batch": "first_grad_gap",
            "altered_token": "gen_logp_shift"}[fault]
    assert want in failing(line)


def test_control_fails_the_limits():
    cell = tiny_cell(NAME)
    kind = SP.load_kind(cell.kind)
    import jax
    got = kind.calibrate(cell, 5, True, jax.profiler.TraceAnnotation)
    limits = cell.limits["limits"]
    checks = C.checks_from(got["control_fp8"], limits)
    assert not C.passed(checks)
    assert C.passed(C.checks_from(
        dict(got["program"], window_compiles=0), limits))
