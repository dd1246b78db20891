"""JAX's persistent compilation cache for the entry points.

A stage-3 job compiles several whole-model programs (SFT and reward
steps, scoring, actor/critic steps, prefill buckets, decode chunks); the
persistent cache lets a later process on the same machine reuse them.
The cache's path is part of its key, so it never moves: the directory
named by ``JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads the
variable itself, and nothing here overrides it), otherwise ``.jax_cache``
at the root of the checkout.  Tests do not call this.

A process that sees more than one device compiles afresh instead.  This
is a workaround whose cause is open: on a four-chip TPU v5e host, runs of
the disaggregated rollout/train split that could load executables back
from the cache halted the training chips at their launch barrier
("Invalid logical z: enhanced-barrier-parent-phase-1"), while runs with
the cache off, or with the enhanced launch barrier off, completed.  No
run has isolated which of the two is at fault, so the rule is kept wide
until one does.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Turn the persistent compilation cache on for a one-device process
    and off for a multi-device one; returns the directory in use, or None
    when it is off."""
    if len(jax.devices()) > 1:
        jax.config.update("jax_enable_compilation_cache", False)
        return None
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
