"""Reduction of a profiler trace to device busy time, kernel time and
idle gaps attributed to what the host was doing.

The harness wraps the traced part of a run in a ``bench.traced``
annotation and each call into the system in its own annotation
(``stage3.generate``, ``stage3.score``, ...).  From the trace:

- window: the ``bench.traced`` span;
- busy: the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices;
- ops: device time and call count by operation name;
- idle gaps: the holes in the busy union, each labelled with the
  innermost host event that covers its midpoint;
- modules: the programs that ran on a device (its ``XLA Modules`` line,
  one event per executed program, ``jit_<name>(<hash>)``), clipped to
  the window;
- idle by span: the same holes, each labelled with the innermost span of
  the program's tracer (``repro.obs``, on the host plane while it is
  enabled) or of the harness, else ``outside spans``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.traced"
MODULES_LINE = "XLA Modules"
# the layers of the program's spans (``gen.decode``, ...), and the
# harness's own (``stage3.generate``, ...)
SPAN_LAYERS = ("stage3", "ppo", "gen", "hybrid", "ema", "replay",
               "pipeline")
OUTSIDE = "outside spans"
# lines of a TPU plane that hold whole programs or steps rather than ops
_NOT_OPS = (MODULES_LINE, "Steps", "XLA TraceMe", "Framework Ops",
            "Framework Name Scope", "Source code", "Host Offload Ops")


@dataclasses.dataclass
class Op:
    name: str
    detail: str          # the name and the text of its stats, for matching
    start: float         # seconds on the trace's clock
    end: float


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    ops: Dict[str, Tuple[int, float]]        # name -> (calls, seconds)
    device_ops: List[Op]
    idle_gaps: List[Tuple[str, float]]       # (host span, seconds)
    host_spans: Dict[str, List[Tuple[float, float]]]
    modules: List[Tuple[str, float, float]]  # (module, start s, end s)
    idle_by_span: List[Tuple[str, float]]    # (program span, seconds)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _detail(ev) -> str:
    parts = [ev.name]
    try:
        for st in ev.stats:
            parts.append(str(st[1]) if isinstance(st, tuple) else str(st))
    except Exception:      # stats of unknown shape only narrow matching
        pass
    return " ".join(parts)


def _is_span(name: str) -> bool:
    return "." in name and name.split(".", 1)[0] in SPAN_LAYERS


def _label_holes(merged, w0, w1, spans, outside, into) -> None:
    """Add each hole of the busy union ``merged`` in [w0, w1] to ``into``
    under the shortest of ``spans`` (name, start, end) covering its
    midpoint, else under ``outside``."""
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = 0.5 * (a + b)
        cover = [(e - s, n) for n, s, e in spans if s <= mid <= e]
        label = min(cover)[1] if cover else outside
        into[label] = into.get(label, 0.0) + (b - a)


def _by_size(d: Dict[str, float], n: int) -> List[Tuple[str, float]]:
    return sorted(((k, v / n) for k, v in d.items()), key=lambda kv: -kv[1])


def reduce_events(planes) -> TraceSummary:
    """``planes``: iterable of (plane name, [(line name, [event])]) where
    an event has ``name``, ``start_ns``, ``duration_ns`` and ``stats``."""
    host_spans: Dict[str, List[Tuple[float, float]]] = {}
    per_device: Dict[str, List[Op]] = {}
    modules: List[Tuple[str, float, float]] = []
    for pname, plines in planes:
        is_dev = pname.startswith("/device:TPU:") and "SparseCore" not in pname
        for lname, events in plines:
            for ev in events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if is_dev and lname == MODULES_LINE:
                    modules.append((ev.name, s, e))
                elif is_dev and lname not in _NOT_OPS:
                    per_device.setdefault(pname, []).append(
                        Op(ev.name, _detail(ev), s, e))
                elif pname.startswith("/host:"):
                    host_spans.setdefault(ev.name, []).append((s, e))
    if WINDOW_SPAN not in host_spans:
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    w0, w1 = host_spans[WINDOW_SPAN][0]
    window = w1 - w0
    busy_total, ops = 0.0, {}
    all_ops: List[Op] = []
    gaps: Dict[str, float] = {}
    by_span: Dict[str, float] = {}
    spans = [(n, s, e) for n, v in host_spans.items() if n != WINDOW_SPAN
             for s, e in v]
    program_spans = [x for x in spans if _is_span(x[0])]
    for dev, dev_ops in per_device.items():
        clipped = [(max(o.start, w0), min(o.end, w1)) for o in dev_ops
                   if o.end > w0 and o.start < w1]
        merged = _union(clipped)
        busy_total += sum(e - s for s, e in merged)
        for o in dev_ops:
            if o.end > w0 and o.start < w1:
                all_ops.append(o)
                c, t = ops.get(o.name, (0, 0.0))
                ops[o.name] = (c + 1, t + (o.end - o.start))
        _label_holes(merged, w0, w1, spans, "host (no harness span)", gaps)
        _label_holes(merged, w0, w1, program_spans, OUTSIDE, by_span)
    n_dev = max(len(per_device), 1)
    return TraceSummary(
        window_s=window, busy_s=busy_total / n_dev, n_devices=len(per_device),
        ops=ops, device_ops=all_ops, idle_gaps=_by_size(gaps, n_dev),
        host_spans=host_spans,
        modules=[(n, max(s, w0), min(e, w1)) for n, s, e in modules
                 if e > w0 and s < w1],
        idle_by_span=_by_size(by_span, n_dev))


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = [(pl.name, [(ln.name, list(ln.events)) for ln in pl.lines])
              for pl in pd.planes]
    return reduce_events(planes)


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(found, key=os.path.getmtime)


def breakdown(s: TraceSummary, top: int = 10) -> dict:
    ops = sorted(s.ops.items(), key=lambda kv: -kv[1][1])[:top]
    return {"device_ops": [[n, t / max(s.n_devices, 1)] for n, (_, t) in ops],
            "idle_gaps": [[n, t] for n, t in s.idle_gaps[:top]],
            "idle_by_span": [[n, t] for n, t in s.idle_by_span[:top]]}
