#!/usr/bin/env python3
"""Benchmark harness: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell by name (``bench/lib/spec.py``), refuses to run
anywhere but on as many TPU chips as the cell asks for, hands the cell to
the module of its traffic's kind (``bench/kinds/<kind>.py``), which builds
the system from the seed, warms up every shape the window uses and
measures for ``--seconds``; then checks what the timed path produced
against the plain reference.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each compared number beside its limit.  The same numbers are
the last lines of standard error.

JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``, a
fixed path, so only the first run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

_PROCESS_T0 = time.perf_counter()

import argparse
import contextlib
import json
import math
import os
import shutil
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.lib import compare as C          # noqa: E402
from bench.lib import device as DV          # noqa: E402
from bench.lib import spec as SP            # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "bench" / ".trace"


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax():
    # libtpu logs to a fixed /tmp path unless told otherwise; a run keeps
    # what it writes inside its checkout and its own directories
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    return jax


class _TraceBox:
    summary = None


@contextlib.contextmanager
def tracing(jax):
    """Profile the block inside the window annotation; the box it yields
    gets the trace's reduction (``bench.lib.trace``) as ``summary`` on
    exit, and the trace files are removed."""
    from bench.lib import trace as TR
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    box = _TraceBox()
    jax.profiler.start_trace(str(TRACE_DIR))
    try:
        with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
            yield box
    finally:
        jax.profiler.stop_trace()
    box.summary = TR.reduce_file(TR.find_xplane(str(TRACE_DIR)))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)


class Context:
    """What a traffic kind's ``run`` gets: the cell, the run's arguments, and the
    harness's clocks, annotations, tracing and memory reading."""

    def __init__(self, cell, args, jax, devs, clog, t0):
        self.cell, self.jax, self.devs, self.clog = cell, jax, devs, clog
        self.seed, self.seconds, self.trace = (args.seed, args.seconds,
                                               bool(args.trace))
        self.t0 = t0
        self.setup_s = None
        self.window_compiles = None
        self.memory_peak = self.memory_limit = None
        self._mark = None

    def annotate(self, name):
        return self.jax.profiler.TraceAnnotation(name)

    def setup_done(self):
        """Set-up ends; what runs next is timed."""
        self.setup_s = time.perf_counter() - self.t0
        self._mark = self.clog.mark()
        self.setup_compiles = self.clog.since((0, 0.0, 0))

    def window_done(self):
        self.t_window_end = time.perf_counter()
        d = self.clog.since(self._mark)
        self.window_compiles = d["compiles"] + d["cache_hits"]

    def read_memory(self):
        self.memory_peak = DV.memory_peak_bytes(self.devs)
        self.memory_limit = DV.memory_limit_bytes(self.devs)

    def tracing(self):
        return tracing(self.jax)


def per_layer_metrics(cell, record, peaks, root):
    out = {}
    rec = dict(record, cell=cell, peaks=peaks)
    for m in cell.per_layer:
        value = SP.load_metric_reader(m.name, root)(rec)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out


def _finite(v):
    return v if isinstance(v, (int, float)) and math.isfinite(v) else None


def run_cell(cell, args, jax, devs, peaks, t0=_PROCESS_T0) -> dict:
    """Drive one run of ``cell`` on ``devs`` and assemble its result line.
    ``peaks`` is None where the device has no published peaks (a CPU
    run in the tests): the metrics that need them are then left out."""
    device = DV.describe(devs)
    from bench.lib.compile_log import CompileLog
    clog = CompileLog()
    ctx = Context(cell, args, jax, devs, clog, t0)
    kind = SP.load_kind(cell.kind, cell.root)
    res = kind.run(ctx)
    t_end = time.perf_counter()

    numbers = dict(res["numbers"], window_compiles=ctx.window_compiles)
    checks = C.checks_from(numbers, cell.limits["limits"])
    device["memory_peak_bytes"] = ctx.memory_peak
    rec = res["record"]
    line = {"correct": C.passed(checks) and res["failed"] == 0,
            "attempted": res["attempted"], "failed": res["failed"]}
    if args.trace:
        s = rec.get("trace")
        line["metrics"] = per_layer_metrics(cell, rec, peaks, cell.root)
        device["busy_s"] = s.busy_s
        device["window_s"] = s.window_s
        from bench.lib.trace import breakdown
        line["device"] = device
        line["breakdown"] = breakdown(s)
    else:
        e2e = dict(res["end_to_end"], setup_s=ctx.setup_s)
        units = {m.name: m.unit for m in cell.end_to_end}
        line["metrics"] = {k: {"value": float(v), "unit": units[k]}
                           for k, v in e2e.items() if k in units}
        line["device"] = device
    line["checks"] = {n: {"value": _finite(v), "limit": lim}
                      for n, v, lim in checks}
    line["_checks"] = checks
    sc = ctx.setup_compiles
    line["_diag"] = (f"setup_s={ctx.setup_s:.3f} setup_compiles="
                     f"{sc['compiles']} compile_s={sc['compile_s']:.3f} "
                     f"cache_hits={sc['cache_hits']} window_compiles="
                     f"{ctx.window_compiles} after_window_s="
                     f"{t_end - ctx.t_window_end:.3f} memory_limit_bytes="
                     f"{ctx.memory_limit}"
                     + "".join(f" {k}(min/median/max)={min(v):.4f}/"
                               f"{statistics.median(v):.4f}/{max(v):.4f}"
                               for k, v in rec.get("spans", {}).items() if v))
    return line


def main(argv=None) -> int:
    args = parse(argv)
    try:
        cell = SP.load_cell(args.workload)
    except SP.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    jax = configure_jax()
    try:
        devs = DV.require_chips(jax, cell.chips)
        peaks = DV.peaks_for(devs[0].device_kind)
    except (DV.NoChip, KeyError) as e:
        print(f"bench: refusing to measure: {e}", file=sys.stderr)
        return 3
    line = run_cell(cell, args, jax, devs, peaks)
    checks = line.pop("_checks")
    print(f"bench: {line.pop('_diag')}", file=sys.stderr)
    sys.stdout.flush()
    C.report(checks, sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line))
    sys.stdout.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
