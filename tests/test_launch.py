"""The launchers' mesh: built from the devices found, every axis Auto."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.core.txn import Mode, Protector
from repro.launch.mesh import device_mesh_shape, make_mesh


@pytest.mark.parametrize("n_devices,shape", [(1, (1, 1)), (2, (2, 1)),
                                             (4, (4, 1)), (8, (4, 2))])
def test_device_mesh_shape(n_devices, shape):
    """One chip is (1, 1), a four-chip host is a four-rank zone, and the
    eight forced CPU host devices keep the tests' (4, 2)."""
    assert device_mesh_shape(n_devices) == shape


def test_launch_mesh_on_host_devices_is_auto():
    mesh = make_mesh(device_mesh_shape(len(jax.devices())))
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    assert set(mesh.axis_types) == {AxisType.Auto}


def test_parity_on_one_chip_raises_actionable_redundancy_error():
    """A (1, 1) mesh holds a zone of one rank: MLPC must refuse at build
    time, naming the redundancy, instead of failing inside a commit."""
    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    state = {"w": jax.ShapeDtypeStruct((8, 64), jnp.float32)}
    with pytest.raises(ValueError, match="redundancy=1 on a zone of 1"):
        Protector(mesh, state, {"w": P("data", "model")}, mode=Mode.MLPC)
    # the modes a single chip can run still build
    Protector(mesh, state, {"w": P("data", "model")}, mode=Mode.ML)
