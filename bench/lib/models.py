"""The system's model configuration for a model of a configuration file."""
from __future__ import annotations

# sizes and numerics a configuration file gives each model, as the
# system's ``ModelConfig`` names them
PROGRAM_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab_size", "rope_theta", "rms_eps", "logit_chunk",
                "param_dtype", "compute_dtype")


def model_config(name: str, sizes: dict, **extra):
    """A dense decoder ``ModelConfig`` with the file's sizes."""
    from repro.models.config import ModelConfig
    return ModelConfig(name=name, arch_type="dense", **extra,
                       **{k: sizes[k] for k in PROGRAM_KEYS})
