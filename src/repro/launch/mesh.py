"""Production meshes.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state — the dry-run process
sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any
jax import and only then builds the mesh.

Target hardware: TPU v5e — 197 bf16 TFLOP/s, 819 GB/s HBM, ~50 GB/s/link
ICI, 16 GiB HBM per chip.  Single pod = 16x16 = 256 chips; multi-pod =
2 pods = 512 chips with a leading "pod" axis (DCN-ish slower links).
"""
from __future__ import annotations

import jax

# v5e hardware constants used by the roofline analysis
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link
HBM_BYTES = 16 * 2 ** 30     # per chip


def _mesh(shape, axes, devices=None):
    # every axis Auto: the model code places activations with GSPMD
    # sharding constraints, which explicit-sharding axes would reject
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Mesh over whatever devices exist (CPU tests: 1 device)."""
    n = len(jax.devices())
    return _mesh((n // model, model), ("data", "model"))


def make_mesh(dp: int, tp: int):
    """Explicit DP×TP ``("data", "model")`` mesh over the FIRST ``dp*tp``
    devices — unlike :func:`make_local_mesh` it does not require the
    requested shape to cover every device, so a simulated 8-device host
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``) can carry a
    2×2 mesh for the CI multi-device matrix."""
    n = dp * tp
    devs = jax.devices()
    if n > len(devs):
        raise ValueError(f"mesh {dp}x{tp} needs {n} devices, "
                         f"have {len(devs)}")
    return _mesh((dp, tp), ("data", "model"), devices=devs[:n])


def mesh_from_spec(spec: str):
    """Parse a ``--mesh dp,tp`` flag (e.g. ``"2,4"``) into a mesh."""
    parts = [int(x) for x in spec.split(",")]
    if len(parts) != 2 or any(p < 1 for p in parts):
        raise ValueError(f"--mesh expects 'dp,tp' (got {spec!r})")
    return make_mesh(*parts)


def _submesh_shape(spec, default_axis: str, flag: str):
    """A disaggregated sub-mesh spec: a bare int ``n`` spreads the n
    devices over the natural axis for that group (``model``/TP for
    rollout — generation wants the whole model resident; ``data``/DP
    for training — the PPO step batch-parallelizes), and an explicit
    ``"dp,tp"`` string or ``(dp, tp)`` tuple is taken verbatim."""
    if isinstance(spec, str):
        parts = [int(x) for x in spec.split(",")]
        if len(parts) == 1:
            spec = parts[0]
        elif len(parts) == 2:
            spec = tuple(parts)
        else:
            raise ValueError(f"{flag} expects 'n' or 'dp,tp' "
                             f"(got {spec!r})")
    if isinstance(spec, (tuple, list)):
        dp, tp = (int(spec[0]), int(spec[1])) if len(spec) == 2 else (0, 0)
        if dp < 1 or tp < 1:
            raise ValueError(f"{flag} expects positive 'dp,tp' "
                             f"(got {spec!r})")
        return dp, tp
    n = int(spec)
    if n < 1:
        raise ValueError(f"{flag} needs >= 1 device (got {n})")
    return (1, n) if default_axis == "model" else (n, 1)


def make_disaggregated_meshes(*, rollout, train):
    """Carve ONE host's devices into a dedicated rollout (generation)
    mesh and a DISJOINT training mesh — the disaggregated async-RLHF
    topology (OpenRLHF-style), replacing the hybrid engine's
    time-shared mesh.  ``rollout``/``train`` are each an int device
    count or an explicit ``"dp,tp"`` spec (see :func:`_submesh_shape`);
    the rollout group takes the FIRST devices, the training group the
    next ones, e.g. on a simulated 8-device host
    (``XLA_FLAGS=--xla_force_host_platform_device_count=8``)::

        rollout_mesh, train_mesh = make_disaggregated_meshes(
            rollout=6, train=2)         # 1x6 TP gen | 2x1 DP train

    Returns ``(rollout_mesh, train_mesh)``; raises ``ValueError`` if
    the two groups would oversubscribe the host."""
    r_dp, r_tp = _submesh_shape(rollout, "model", "--rollout-mesh")
    t_dp, t_tp = _submesh_shape(train, "data", "--train-mesh")
    nr, nt = r_dp * r_tp, t_dp * t_tp
    devs = jax.devices()
    if nr + nt > len(devs):
        raise ValueError(
            f"disaggregated meshes need {nr} rollout + {nt} train "
            f"= {nr + nt} devices, have {len(devs)}")
    rollout_mesh = _mesh((r_dp, r_tp), ("data", "model"),
                         devices=devs[:nr])
    train_mesh = _mesh((t_dp, t_tp), ("data", "model"),
                       devices=devs[nr:nr + nt])
    return rollout_mesh, train_mesh
