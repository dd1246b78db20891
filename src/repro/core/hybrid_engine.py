"""DeepSpeed Hybrid Engine (DeepSpeed-HE), TPU-native.

The paper's core systems idea: RLHF stage 3 alternates between an
inference-dominated *generation* phase and a compute-bound *training*
phase.  Running generation under the training layout (ZeRO-3) costs one
all-gather of every weight shard per layer **per generated token**; the
Hybrid Engine instead reshards the actor **once per phase**:

    train layout  = ZeRO-3 + TP   (params sharded over data & model axes)
    infer layout  = TP only       (params replicated over data axes)

In JAX the mode switch is a jitted identity function with
``out_shardings`` set to the other layout — XLA emits exactly one
all-gather (train->infer) or one slice (infer->train) per parameter, which
is the "seamless transition" of Fig. 2 as a first-class collective.  The
analytic methods below quantify the win and feed the Fig. 5/6 benchmark
analogues.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding

from repro import obs
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.sharding import strategy as S


def _tree_device_bytes(tree) -> int:
    """Bytes this tree actually occupies across addressable devices —
    replicas counted once per device (the quantity a reshard changes)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            total += leaf.size * leaf.dtype.itemsize
        else:
            total += sum(s.data.size * s.data.dtype.itemsize
                         for s in shards)
    return total


@dataclasses.dataclass
class HybridEngine:
    cfg: ModelConfig
    mesh: Mesh
    train_strategy: str = "zero3"
    infer_strategy: str = "tp"
    zero: int = 1                      # ZeRO stage for the optimizer state

    def __post_init__(self):
        self.train_pspecs = S.param_pspecs(self.cfg, self.mesh,
                                           self.train_strategy)
        self.infer_pspecs = S.param_pspecs(self.cfg, self.mesh,
                                           self.infer_strategy)
        ns = lambda ps: jax.tree.map(
            lambda p: NamedSharding(self.mesh, p), ps)
        self.train_shardings = ns(self.train_pspecs)
        self.infer_shardings = ns(self.infer_pspecs)
        self._to_infer = jax.jit(obs.named("hybrid_to_inference",
                                           lambda p: p),
                                 out_shardings=self.infer_shardings)
        self._to_train = jax.jit(obs.named("hybrid_to_train", lambda p: p),
                                 out_shardings=self.train_shardings)
        # measured (not estimated) stats of the LAST phase transition:
        # wall time of its ``hybrid.<direction>`` span, which ends at
        # block_until_ready, plus the per-device byte delta read off the
        # actual output arrays' shards
        self.last_reshard_stats: dict = {}
        self._warm: set = set()        # directions already traced/compiled

    # ---------------------------------------------------------------- #
    # phase transitions (the Hybrid Engine switch)
    # ---------------------------------------------------------------- #
    def _reshard(self, fn, params, direction: str):
        in_bytes = _tree_device_bytes(params)
        first = direction not in self._warm
        with obs.span(f"hybrid.{direction}") as sp, self.mesh:
            out = fn(params)
            jax.block_until_ready(out)
        dt = sp.seconds
        self._warm.add(direction)
        out_bytes = _tree_device_bytes(out)
        # an all-gather materializes exactly the replica bytes the input
        # didn't hold (receive-side traffic); the reverse slice frees
        # them.  `first_call` marks a timing that includes trace+compile
        # of the reshard graph — consumers comparing transfer cost
        # should look at steady-state (first_call=False) samples.
        self.last_reshard_stats = {
            "direction": direction,
            "seconds": dt,
            "first_call": first,
            "in_bytes": in_bytes,
            "out_bytes": out_bytes,
            "gathered_bytes": max(out_bytes - in_bytes, 0),
            "freed_bytes": max(in_bytes - out_bytes, 0),
        }
        return out

    def to_inference(self, params):
        """Enter generation mode: ONE all-gather pass over the params,
        measured (bytes + wall time) into ``last_reshard_stats``.  On one
        device both layouts are the same, and ``params`` are handed over
        as they are: no copy of the actor is made for generation."""
        fn = (lambda p: p) if self.mesh.size == 1 else self._to_infer
        return self._reshard(fn, params, "to_inference")

    def to_train(self, params):
        """Back to training mode (a slice per param — no communication
        beyond discarding replicas)."""
        return self._reshard(self._to_train, params, "to_train")

    # ---------------------------------------------------------------- #
    # training-side layouts (the sharded PPO step consumes these)
    # ---------------------------------------------------------------- #
    def train_state_shardings(self, cfg: Optional[ModelConfig] = None,
                              specs=None):
        """NamedShardings for a full TrainState in the training layout:
        ``train_strategy`` params, ``zero``-staged optimizer moments.
        ``specs`` overrides the param-spec tree (the critic's value-head
        structure)."""
        return S.train_state_shardings(cfg or self.cfg, self.mesh,
                                       self.train_strategy, zero=self.zero,
                                       specs=specs)

    def shard_train_state(self, state, cfg: Optional[ModelConfig] = None,
                          specs=None):
        """Place a TrainState into the training layout (one collective)."""
        return jax.device_put(state,
                              self.train_state_shardings(cfg, specs))

    # ---------------------------------------------------------------- #
    # generation engine (the serving-grade experience-generation path)
    # ---------------------------------------------------------------- #
    def generation_engine(self, cfg=None, **gen_kwargs):
        """Build a :class:`repro.serving.engine.GenerationEngine` for this
        actor.  ``cfg`` overrides the engine's model config — the PPO
        trainer uses it to flip generation-only cache options
        (``kv_quant``) without touching the training-side config; it
        must describe the same parameters (same specs/shapes).
        The engine expects params already in the inference layout:
        call :meth:`to_inference` once per phase and pass the result to
        ``engine.generate`` / ``engine.serve`` / ``engine.core`` — that
        pairing is the Hybrid Engine contract (one reshard, then a
        serving-grade decode loop under the TP layout).  ``engine.core``
        returns the stepwise request-level core
        (:class:`repro.serving.engine.EngineCore`): ``add_request`` /
        ``step`` / ``cancel`` with per-request sampling params, used by
        both the serve launcher and ragged PPO experience generation.

        On a multi-device mesh the engine is handed the mesh so its KV
        cache is laid out per-device (batch over ``data``, KV length
        over ``model`` where divisible) to match the TP params it
        consumes; a 1-device mesh keeps the historical unsharded
        graphs."""
        from repro.serving.engine import GenerationEngine
        mesh = self.mesh if np.prod(
            list(self.mesh.shape.values())) > 1 else None
        return GenerationEngine(cfg if cfg is not None else self.cfg,
                                mesh=mesh, **gen_kwargs)

    # ---------------------------------------------------------------- #
    # analytics (feed benchmarks/phase_breakdown + effective_throughput)
    # ---------------------------------------------------------------- #
    def param_bytes(self) -> int:
        specs = T.param_specs(self.cfg)
        return int(sum(
            np.prod(s.shape) for s in jax.tree.leaves(
                specs, is_leaf=lambda x: hasattr(x, "shape")))
            * self.cfg.pdtype.itemsize)

    def reshard_bytes_per_phase(self) -> int:
        """Bytes all-gathered by ONE train->infer transition (global)."""
        dp = S.data_axes(self.mesh)
        n_dp = int(np.prod([self.mesh.shape[a] for a in dp])) if dp else 1
        # each param sharded over data gathers (n_dp - 1)/n_dp of its bytes
        # on each of the n_dp replicas
        return int(self.param_bytes() * (n_dp - 1))

    def naive_generation_gather_bytes(self, n_tokens: int) -> int:
        """Baseline (ZeRO-3 generation without HE): every decode step
        re-gathers every sharded param."""
        return self.reshard_bytes_per_phase() * n_tokens

    def hybrid_speedup_estimate(self, n_tokens: int) -> float:
        naive = self.naive_generation_gather_bytes(n_tokens)
        he = self.reshard_bytes_per_phase()
        return naive / max(he, 1)
