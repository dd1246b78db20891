#!/usr/bin/env python3
"""The stage-3 iteration split from inside the program, on the chip, in
one process: the program's tracer on and off, a profiler trace read per
named program, and where the iterations that stall lose their time.

    python3 bench/tools/stage3_split.py --workload <name> --seed <n> \\
        --seconds 20 --rounds 2 [--out split.json]

Builds the cell's ``Program`` (``bench/kinds/ppo_fixed.py``) from the
seed and warms it up, then runs ``2 * rounds`` windows of ``--seconds``
with the tracer (``repro.obs``) off and on in turn: ``ppo_iter_s`` of
each and its slow iterations, and over the windows with it on the
largest stall of a ``ppo.*`` root span and the child span that took it
(``bench.lib.programs.stall``).
Last it traces the cell's ``trace_iterations`` as a ``--trace 1`` run
does (``ppo_fixed.traced``) and reads from the trace: device seconds per
named program per iteration, ``gen_decode`` per decode step
(``bench.lib.programs``, as the per-layer readers do), and the idle
seconds by the innermost program span (``bench.lib.trace``).  Prints one
JSON object.  Benchmark runs never call this.
"""
from __future__ import annotations

import argparse
import functools
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run as R              # noqa: E402
from bench.lib import device as DV      # noqa: E402
from bench.lib import programs as PG    # noqa: E402
from bench.lib import spec as SP        # noqa: E402
from bench.lib import trace as TR       # noqa: E402

PROGRAMS = ("hybrid_to_inference", "gen_prefill", "gen_decode",
            "ppo_make_experience", "ppo_actor_step", "ppo_critic_step",
            "ema_update")


def window(prog, i, seconds, annotate, obs, on):
    """Iterations for ``seconds`` with the tracer on or off: per iteration
    its index and seconds; and the next iteration's index."""
    (obs.enable if on else obs.disable)()
    rows = []
    t0 = time.perf_counter()
    try:
        while True:
            t = time.perf_counter()
            prog.iteration(i, annotate)
            rows.append((i, time.perf_counter() - t))
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
    finally:
        obs.disable()
    return rows, i


def host_medians(spans):
    by: dict = {}
    for sp in spans:
        by.setdefault(sp.name, []).append((sp.end_ns - sp.start_ns) * 1e-9)
    return {k: statistics.median(v) for k, v in sorted(by.items())}


def split(cell, seed: int, seconds: float, rounds: int, jax) -> dict:
    """Everything the module docstring lists, for ``cell`` on ``seed``."""
    from repro import obs
    annotate = jax.profiler.TraceAnnotation
    kind = SP.load_kind(cell.kind, cell.root)
    tr = cell.traffic
    prog = kind.Program(cell, seed)
    i = 0
    for _ in range(tr["check_steps"]):
        prog.iteration(i, annotate)
        i += 1

    out = {"workload": cell.name, "seed": seed,
           "prompt_len": tr["prompt_len"], "gen_len": tr["gen_len"],
           "windows": []}
    obs.reset()
    for r in range(rounds):
        for on in ((False, True) if r % 2 == 0 else (True, False)):
            rows, i = window(prog, i, seconds, annotate, obs, on)
            times = [t for _, t in rows]
            med = statistics.median(times)
            out["windows"].append({
                "tracer": on, "iterations": len(times),
                "ppo_iter_s": sum(times) / len(times), "median_s": med,
                "max_s": max(times),
                # iterations 10 ms or more over the median: (index, seconds)
                "slow": [r for r in rows if r[1] > med + 0.01]})
    snap = obs.snapshot()
    out["stall"] = PG.stall(snap["spans"])
    out["host_span_median_s"] = host_medians(snap["spans"])

    # ---- the traced iterations, tracer on ---- #
    n = tr["trace_iterations"]
    s, program = kind.traced(prog, i, n, functools.partial(R.tracing, jax),
                             annotate)
    steps = program["counters"].get("gen.decode_steps", 0)
    idle = s.idle_by_span
    idle_s = sum(t for _, t in idle)
    out["trace"] = {
        "window_s": s.window_s, "busy_s": s.busy_s,
        "programs": {k: [c, t / n] for k, (c, t) in sorted(
            PG.seconds_by_program(s.modules).items(),
            key=lambda kv: -kv[1][1])},
        "per_iteration_s": {p: PG.per_iteration(s.modules, p, n)
                            for p in PROGRAMS},
        "decode_step_ms": PG.decode_step_ms(s.modules, program["counters"]),
        "decode_steps": steps,
        "idle_by_span": idle,
        "idle_in_spans_share": (1.0 - dict(idle).get(TR.OUTSIDE, 0.0)
                                / idle_s) if idle_s else None,
        "idle_gaps": s.idle_gaps[:10],
        "host_span_median_s": host_medians(program["spans"]),
    }
    obs.reset()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rounds", type=int, default=2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = SP.load_cell(args.workload)
    jax = R.configure_jax()
    try:
        DV.require_chips(jax, cell.chips)
    except DV.NoChip as e:
        print(f"stage3_split: {e}", file=sys.stderr)
        return 3
    line = json.dumps(split(cell, args.seed, args.seconds, args.rounds,
                            jax), default=str)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
