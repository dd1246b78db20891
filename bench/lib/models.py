"""The system's model configuration for a model of a configuration file.

Every key of a model entry names a field of the system's ``ModelConfig``
(``arch_type`` is ``"dense"`` unless the entry says otherwise); fields
the entry leaves out take ``ModelConfig``'s defaults.  A key that is no
field is refused, so that a misspelt size cannot pass as a default.
"""
from __future__ import annotations

import dataclasses

from bench.lib.spec import SpecError


def model_config(name: str, sizes: dict):
    """The ``ModelConfig`` named ``name`` with the entry ``sizes``."""
    from repro.models.config import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)} - {"name"}
    unknown = sorted(set(sizes) - fields)
    if unknown:
        raise SpecError(f"model {name!r}: {unknown} are not fields of the "
                        f"system's ModelConfig")
    return ModelConfig(name=name, **{"arch_type": "dense", **sizes})
