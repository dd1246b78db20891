"""Tiny versions of the benchmark's cells for CPU tests: the real cell's
traffic kind, limits and metrics with every size cut down so that a run
takes seconds on the CPU."""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.lib import spec as SP  # noqa: E402

TINY_MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
              "head_dim": 16, "d_ff": 128, "vocab_size": 512}

TINY_TRAFFIC = {
    # enough sampled tokens (16 x 96 x 3) that the sampling noise of
    # gen_logp_shift stays well under its limit
    "ppo_fixed": {"batch": 16, "prompt_len": 8, "gen_len": 96,
                  "check_steps": 3, "trace_iterations": 1},
}


def tiny_cell(name: str, **traffic_over) -> SP.Cell:
    cell = SP.load_cell(name)
    cfg = dict(cell.config)
    cfg["models"] = {r: dict(m, **TINY_MODEL)
                     for r, m in cell.config["models"].items()}
    tr = dict(cell.traffic, **TINY_TRAFFIC[cell.traffic["kind"]])
    tr.update(traffic_over)
    return dataclasses.replace(cell, config=cfg, traffic=tr)


def run_tiny(cell, seed=7, seconds=1.0, trace=0):
    """Drive one run of ``cell`` on the CPU, skipping the look for a chip."""
    import jax
    from bench import run as R
    args = argparse.Namespace(workload=cell.name, seed=seed,
                              seconds=seconds, trace=trace)
    return R.run_cell(cell, args, jax, jax.devices()[:1], None)


def run_kind(cell, seed=7, seconds=1.0, trace=0) -> dict:
    """What the kind's ``run`` returns for one run of ``cell`` on the CPU
    (its record among it), skipping the look for a chip."""
    import jax
    from bench import run as R
    from bench.lib.compile_log import CompileLog
    args = argparse.Namespace(workload=cell.name, seed=seed,
                              seconds=seconds, trace=trace)
    ctx = R.Context(cell, args, jax, jax.devices()[:1], CompileLog(),
                    time.perf_counter())
    return SP.load_kind(cell.kind, cell.root).run(ctx)
