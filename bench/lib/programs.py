"""Device time per named program, idle time per program span, and the
iterations that stall, from a profiler trace and the program's tracer.

The stage-3 path runs each jitted program under a name of its own
(``repro.obs.named``): the ``XLA Modules`` line of a TPU plane holds one
event per executed program, ``jit_<name>(<hash>)``.  The program's spans
(``repro.obs.span``, ``layer.step``) sit on the host plane as annotations,
on the same clock.  ``planes`` is what ``bench.lib.trace.reduce_events``
reads: (plane name, [(line name, [event])]) with events that have
``name``, ``start_ns`` and ``duration_ns``.
"""
from __future__ import annotations

import re
import statistics
from typing import Dict, List, Optional, Tuple

from bench.lib import trace as TR

MODULES_LINE = "XLA Modules"
# the layers of the program's spans, and the harness's own
SPAN_LAYERS = ("stage3", "ppo", "gen", "hybrid", "ema", "replay",
               "pipeline")
OUTSIDE = "outside spans"
_MODULE = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")


def program_name(module: str) -> str:
    """``jit_gen_decode(123)`` -> ``gen_decode``."""
    return _MODULE.match(module).group(1)


def modules(planes, w0: float, w1: float) -> List[Tuple[str, float, float]]:
    """(program, start s, end s) of every program that ran on a TPU core
    inside the window [w0, w1] (seconds), clipped to it."""
    out = []
    for pname, plines in planes:
        if not pname.startswith("/device:TPU:") or "SparseCore" in pname:
            continue
        for lname, events in plines:
            if lname != MODULES_LINE:
                continue
            for ev in events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if e > w0 and s < w1:
                    out.append((program_name(ev.name), max(s, w0),
                                min(e, w1)))
    return out


def seconds_by_program(mods) -> Dict[str, Tuple[int, float]]:
    """program -> (runs, device seconds)."""
    out: Dict[str, Tuple[int, float]] = {}
    for name, s, e in mods:
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


def decode_step_ms(mods, decode_steps: float) -> Optional[float]:
    """Device milliseconds of ``gen_decode`` per decode step counted by
    the program (counter ``gen.decode_steps``)."""
    n, t = seconds_by_program(mods).get("gen_decode", (0, 0.0))
    if n == 0 or not decode_steps:
        return None
    return 1e3 * t / decode_steps


def _is_span(name: str) -> bool:
    return name.split(".", 1)[0] in SPAN_LAYERS and "." in name


def idle_by_span(s: "TR.TraceSummary") -> List[Tuple[str, float]]:
    """Idle seconds of the window, each hole in the busy union put down to
    the innermost program or harness span covering its midpoint, else to
    ``outside spans``; largest first.  The busy union is taken over the
    ops of every device at once (one-device cells)."""
    w0, w1 = s.host_spans[TR.WINDOW_SPAN][0]
    busy = TR._union([(max(o.start, w0), min(o.end, w1))
                      for o in s.device_ops if o.end > w0 and o.start < w1])
    spans = [(e - b, n, b, e) for n, v in s.host_spans.items()
             if _is_span(n) for b, e in v]
    out: Dict[str, float] = {}
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        cover = [(d, n) for d, n, s0, s1 in spans if s0 <= mid <= s1]
        label = min(cover)[1] if cover else OUTSIDE
        out[label] = out.get(label, 0.0) + (b - a)
    return sorted(out.items(), key=lambda kv: -kv[1])


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
