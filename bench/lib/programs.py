"""Device time per named program and the iterations that stall, from a
profiler trace and the program's tracer.

The stage-3 path runs each jitted program under a name of its own
(``repro.obs.named``): the ``XLA Modules`` line of a TPU plane holds one
event per executed program, ``jit_<name>(<hash>)``, which
``bench.lib.trace`` keeps as ``TraceSummary.modules``: (module, start s,
end s), clipped to the traced window.  The program's spans
(``repro.obs.span``) and counters come from ``repro.obs.snapshot()``.
The per-layer readers ``stage3.decode_step_ms`` and
``stage3.actor_step_s`` and ``bench/tools/stage3_split.py`` read through
these functions.
"""
from __future__ import annotations

import re
import statistics
from typing import Dict, Optional, Tuple

_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def program_name(module: str) -> str:
    """``jit_gen_decode(123)`` -> ``gen_decode``."""
    return _MODULE.match(module).group(1)


def seconds_by_program(mods) -> Dict[str, Tuple[int, float]]:
    """program -> (runs, device seconds), from (module, start, end)."""
    out: Dict[str, Tuple[int, float]] = {}
    for module, s, e in mods:
        name = program_name(module)
        n, t = out.get(name, (0, 0.0))
        out[name] = (n + 1, t + e - s)
    return out


def per_iteration(mods, name: str, iterations: int) -> Optional[float]:
    """Device seconds of program ``name`` per iteration, or None unless
    it ran exactly once per iteration (one device)."""
    n, t = seconds_by_program(mods).get(name, (0, 0.0))
    if iterations <= 0 or n != iterations:
        return None
    return t / n


def decode_step_ms(mods, counters: Dict[str, float]) -> Optional[float]:
    """Device milliseconds of ``gen_decode`` per decode step that the
    program counted over the same iterations (``counters``: what
    ``repro.obs`` counted, ``gen.decode_steps`` among them)."""
    decode_steps = counters.get("gen.decode_steps", 0)
    n, t = seconds_by_program(mods).get("gen_decode", (0, 0.0))
    if n == 0 or not decode_steps:
        return None
    return 1e3 * t / decode_steps


def stall(spans) -> Optional[dict]:
    """The largest excess of a ``ppo.*`` root span over the median of its
    name, with the child span that took most of it.  ``spans`` are
    ``repro.obs.Span`` records (a ``snapshot()``)."""
    roots: Dict[str, list] = {}
    for sp in spans:
        if sp.parent is None and sp.name.startswith("ppo."):
            roots.setdefault(sp.name, []).append(sp)
    best = None
    for name, v in roots.items():
        med = statistics.median(x.end_ns - x.start_ns for x in v)
        worst = max(v, key=lambda x: x.end_ns - x.start_ns)
        excess = (worst.end_ns - worst.start_ns - med) * 1e-9
        if best is None or excess > best["excess_s"]:
            best = {"excess_s": excess, "span": name, "step": worst.step,
                    "root": worst}
    if best is None:
        return None
    root = best.pop("root")
    kids: Dict[str, list] = {}
    for sp in spans:
        if sp.parent == root.name:
            kids.setdefault(sp.name, []).append(sp)
    child, child_excess = None, 0.0
    for name, v in kids.items():
        inside = [x for x in v if root.start_ns <= x.start_ns
                  and x.end_ns <= root.end_ns]
        if not inside:
            continue
        med = statistics.median(x.end_ns - x.start_ns for x in v)
        ex = (sum(x.end_ns - x.start_ns for x in inside) - med) * 1e-9
        if child is None or ex > child_excess:
            child, child_excess = name, ex
    best.update(child=child, child_excess_s=child_excess)
    return best
