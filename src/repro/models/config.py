"""Model/architecture configuration.

A single ``ModelConfig`` describes every architecture family this framework
supports (dense GQA, MoE, MLA, SSM/Mamba2, hybrid, VLM cross-attn, audio
decoder).  The decoder is expressed as a list of *segments*; each segment is
a repeated *unit* of layers (``unit_spec``) whose parameters are stacked on
a leading axis and scanned with ``jax.lax.scan`` — this keeps compile times
flat in depth and is what makes the 512-device dry-runs tractable.
"""
from __future__ import annotations

import dataclasses
import math
from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# Layer kinds.
ATTN = "attn"        # self-attention (GQA / qk-norm / sliding-window / MLA)
SSM = "ssm"          # Mamba2 SSD block
CROSS = "cross"      # cross-attention over encoder (image/audio) embeddings


@dataclass(frozen=True)
class LayerSpec:
    """Static description of one layer inside a scan unit."""
    kind: str = ATTN            # ATTN | SSM | CROSS
    moe: bool = False           # MoE MLP instead of dense MLP
    sliding_window: Optional[int] = None  # per-layer SW override


@dataclass(frozen=True)
class Segment:
    """``n_units`` repetitions of ``unit_spec`` (params stacked, scanned)."""
    unit_spec: Tuple[LayerSpec, ...]
    n_units: int

    @property
    def n_layers(self) -> int:
        return len(self.unit_spec) * self.n_units


@dataclass(frozen=True)
class ModelConfig:
    name: str
    arch_type: str                     # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0                  # 0 -> d_model // n_heads

    # --- attention ---
    qk_norm: bool = False
    rope_theta: float = 10000.0
    # YaRN context extension (DeepSeek-V2's ``rope_scaling`` of type
    # "yarn"): ``yarn_factor`` 0 leaves RoPE as it is
    yarn_factor: float = 0.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_original_len: int = 4096
    sliding_window: Optional[int] = None   # if set, ALL attn layers are SW
    # MLA (DeepSeek-V2 style multi-head latent attention)
    mla: bool = False
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MoE ---
    moe: bool = False
    # experts whose weights this model holds: the first ``n_experts`` of
    # the ``router_experts`` the router scores (0: the router scores the
    # held experts alone).  Held as one chip's share of an
    # expert-parallel layer, the layer computes what its own experts add
    # for every slot routed to them.
    n_experts: int = 0
    router_experts: int = 0
    norm_topk: bool = True             # renormalise the top-k gates
    n_dense_layers: int = 0            # leading dense layers of a MoE stack
    n_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                  # per-expert hidden size
    capacity_factor: float = 1.25
    router_aux_coef: float = 0.01
    router_z_coef: float = 1e-3

    # --- SSM (Mamba2 / SSD) ---
    ssm_state: int = 0                 # N, state size
    ssm_expand: int = 2
    ssm_headdim: int = 64
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_ngroups: int = 1
    attn_every: int = 0                # hybrid: 1 attn layer per `attn_every`

    # --- VLM / audio frontends (STUBBED: embeddings arrive precomputed) ---
    cross_attn_every: int = 0          # vlm: 1 cross-attn block per N layers
    encoder_dim: int = 0               # dim of incoming patch/frame embeds
    encoder_len: int = 0               # number of patch/frame tokens
    embed_inputs: bool = True          # False -> inputs are embeddings

    # --- distribution ---
    # mesh axis names the activations' batch dim is sharded over; set by
    # the launcher (e.g. ("data",) or ("pod", "data")).  Empty = no
    # constraint (single-device tests).
    batch_axes: Tuple[str, ...] = ()
    # mesh axis for activation tensor-parallel constraints (heads of the
    # SSD scan, MoE expert dim); "" = no constraint.
    tp_axis: str = ""
    tp_size: int = 16
    # §Perf "weight-gather-at-use": constrain each weight at its matmul to
    # the data-axes-stripped layout (true ZeRO-3 semantics: all-gather the
    # small weight instead of partial-sum + all-reducing the large
    # activation, which is what GSPMD otherwise emits)
    weight_gather: bool = False
    # the config's programs run partitioned over a multi-device mesh.
    # ``batch_axes`` / ``tp_axis`` imply it; a multi-device generation
    # engine, which places its arrays itself, sets it alone.
    partitioned: bool = False

    # --- numerics / misc ---
    # int8 KV cache (beyond-paper §Perf optimization): halves the decode
    # memory-bound term; per-(token, kv-head) absmax scales
    kv_quant: bool = False
    tie_embeddings: bool = False
    rms_eps: float = 1e-5
    param_dtype: str = "float32"
    compute_dtype: str = "bfloat16"
    # Pallas kernels for attention, decode and norms.  None (the default)
    # chooses by platform (see ``pallas_kernels``); tests set True to run
    # the kernels in interpret mode on the CPU.
    use_pallas: Optional[bool] = None
    remat: bool = True                 # activation checkpointing per unit
    logit_chunk: int = 0               # chunked loss: 0 = off

    def __post_init__(self):
        if self.head_dim == 0 and self.n_heads:
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)

    # ------------------------------------------------------------------ #
    @property
    def pdtype(self):
        return jnp.dtype(self.param_dtype)

    @property
    def cdtype(self):
        return jnp.dtype(self.compute_dtype)

    @property
    def pallas_kernels(self) -> bool:
        """Whether the model layers dispatch to the Pallas kernels: the
        one place the kernel/jnp choice is made.  By default the kernels
        run on a TPU and the jnp code elsewhere.  GSPMD cannot partition
        a Mosaic kernel, so a config whose activations are sharded over a
        mesh (``partitioned``, or ``batch_axes`` / ``tp_axis`` set) runs
        the jnp code."""
        if self.use_pallas is not None:
            return self.use_pallas
        sharded = self.partitioned or bool(self.batch_axes or self.tp_axis)
        return jax.default_backend() == "tpu" and not sharded

    @property
    def n_router(self) -> int:
        """Experts the router scores."""
        return self.router_experts or self.n_experts

    def rope_inv_freq(self, dim: int) -> np.ndarray:
        """Inverse frequencies of RoPE over ``dim`` features; with YaRN,
        DeepSeek-V2's blend: interpolated (``/ yarn_factor``) below the
        correction range, extrapolated above it, a linear ramp between."""
        extra = 1.0 / self.rope_theta ** (np.arange(0, dim, 2) / dim)
        if not self.yarn_factor:
            return extra

        def corr(rot):
            return (dim * math.log(self.yarn_original_len
                                   / (rot * 2 * math.pi))
                    / (2 * math.log(self.rope_theta)))

        lo = max(math.floor(corr(self.yarn_beta_fast)), 0)
        hi = min(math.ceil(corr(self.yarn_beta_slow)), dim - 1)
        if lo == hi:
            hi += 0.001
        ramp = np.clip((np.arange(dim // 2) - lo) / (hi - lo), 0.0, 1.0)
        keep = 1.0 - ramp                   # 1: extrapolate (high freq)
        return extra / self.yarn_factor * (1.0 - keep) + extra * keep

    def _yarn_mscale(self, m: float) -> float:
        if self.yarn_factor <= 1.0 or not m:
            return 1.0
        return 0.1 * m * math.log(self.yarn_factor) + 1.0

    @property
    def rope_mscale(self) -> float:
        """YaRN's factor on RoPE's cos and sin (1 without YaRN)."""
        return (self._yarn_mscale(self.yarn_mscale)
                / self._yarn_mscale(self.yarn_mscale_all_dim))

    @property
    def softmax_mscale(self) -> float:
        """YaRN's factor on attention's softmax scale (1 without YaRN)."""
        return self._yarn_mscale(self.yarn_mscale_all_dim) ** 2

    def softmax_scale(self, dim: int) -> float:
        """Attention's softmax scale for query/key width ``dim``:
        ``softmax_mscale`` / sqrt(dim)."""
        return self.softmax_mscale / math.sqrt(dim)

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    # ------------------------------------------------------------------ #
    def segments(self) -> Tuple[Segment, ...]:
        """Decoder layout as scan segments."""
        moe = self.moe
        if self.arch_type == "ssm":
            return (Segment((LayerSpec(SSM),), self.n_layers),)
        if self.arch_type == "hybrid":
            k = self.attn_every
            assert k > 1
            unit = tuple([LayerSpec(SSM)] * (k - 1) + [LayerSpec(ATTN)])
            n_units = self.n_layers // k
            rem = self.n_layers - n_units * k
            segs = [Segment(unit, n_units)]
            if rem:
                segs.append(Segment((LayerSpec(SSM),), rem))
            return tuple(segs)
        if self.arch_type == "vlm":
            k = self.cross_attn_every
            assert k > 1
            unit = tuple([LayerSpec(ATTN, moe=moe)] * (k - 1)
                         + [LayerSpec(CROSS, moe=moe)])
            n_units = self.n_layers // k
            rem = self.n_layers - n_units * k
            segs = [Segment(unit, n_units)]
            if rem:
                segs.append(Segment((LayerSpec(ATTN, moe=moe),), rem))
            return tuple(segs)
        # dense / moe / audio: a homogeneous stack, or leading dense
        # layers and then the expert layers
        k = self.n_dense_layers if moe else 0
        if k:
            return (Segment((LayerSpec(ATTN),), k),
                    Segment((LayerSpec(ATTN, moe=True),), self.n_layers - k))
        return (Segment((LayerSpec(ATTN, moe=moe),), self.n_layers),)

    def n_params(self) -> int:
        """Analytic parameter count (for roofline MODEL_FLOPS)."""
        from repro.models.transformer import count_params  # lazy import
        return count_params(self)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class InputShape:
    """One of the assigned benchmark input shapes."""
    name: str
    seq_len: int
    global_batch: int
    phase: str                  # "train" | "prefill" | "decode"


INPUT_SHAPES = {
    "train_4k": InputShape("train_4k", 4096, 256, "train"),
    "prefill_32k": InputShape("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": InputShape("decode_32k", 32768, 128, "decode"),
    "long_500k": InputShape("long_500k", 524288, 1, "decode"),
}
