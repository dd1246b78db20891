"""Mesh construction: all-Auto axes and explicit DP×TP meshes."""
import jax
import numpy as np
import pytest

from repro.launch import mesh as M


def test_axistype_present_branch():
    """Every mesh axis is Auto under the installed ``AxisType``
    (explicit-sharding axes would break the GSPMD constraints this repo
    relies on)."""
    m = M.make_mesh(1, 1)
    assert m.axis_types == (jax.sharding.AxisType.Auto,) * 2
    assert dict(m.shape) == {"data": 1, "model": 1}
    assert m.axis_names == ("data", "model")


def test_make_mesh_slices_devices():
    """make_mesh(dp, tp) runs on the FIRST dp*tp devices, so a partial
    mesh works on a host with more simulated devices than the mesh."""
    n = len(jax.devices())
    m = M.make_mesh(n, 1)
    assert dict(m.shape) == {"data": n, "model": 1}
    with pytest.raises(ValueError, match="needs"):
        M.make_mesh(n + 1, 1)


def test_mesh_from_spec_parsing():
    n = len(jax.devices())
    m = M.mesh_from_spec(f"{n},1")
    assert dict(m.shape) == {"data": n, "model": 1}
    for bad in ("2", "1,2,3", "0,1", "-1,1"):
        with pytest.raises(ValueError):
            M.mesh_from_spec(bad)


def test_local_and_production_mesh_shapes():
    m = M.make_local_mesh()
    assert m.axis_names == ("data", "model")
    assert int(np.prod(list(m.shape.values()))) == len(jax.devices())
