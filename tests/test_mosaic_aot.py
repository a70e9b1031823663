"""Ahead-of-time Mosaic compile of the production kernels for a TPU v5e,
on a CPU host: a Mosaic rejection shows up here, before chip time is
spent on it (ISSUE 21).

The CPU tests run the fused kernels in interpret mode, which traces a
different program from the compiled form (``use_dus=not interpret``
switches ``fe.at_add`` from the scatter form to slice-and-concatenate).
``jax.experimental.topologies`` describes a v5e 2x2 host without one
being attached, and ``lower().compile()`` runs the real Mosaic + XLA:TPU
compile for it (libtpu is installed; no device is opened). Slow-marked:
about half a minute per kernel.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

pytestmark = pytest.mark.slow

TILE = 256


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu on this host
        pytest.skip(f"no TPU compiler on this host: {e!r}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert len(topo.devices) == 4
    return topo


def _compile_single(topo, fn, n_planes, rows=32, extra=()):
    one = SingleDeviceSharding(topo.devices[0])
    plane = jax.ShapeDtypeStruct((rows, TILE), jnp.uint8, sharding=one)
    args = (plane,) * n_planes + tuple(
        jax.ShapeDtypeStruct(shape, dtype, sharding=one)
        for shape, dtype in extra)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_ed25519_kernel_compiles_for_v5e(v5e):
    from tmtpu.tpu import kernel as tk

    _compile_single(
        v5e, lambda a, b, c, d: tk.verify_compact_kernel(
            a, b, c, d, tile=TILE, interpret=False), 4)


def test_sr25519_kernel_compiles_for_v5e(v5e):
    from tmtpu.tpu import kernel as tk

    _compile_single(
        v5e, lambda a, b, c, d: tk.sr_verify_compact_kernel(
            a, b, c, d, tile=TILE, interpret=False), 4)


def test_secp256k1_kernel_compiles_for_v5e(v5e):
    from tmtpu.tpu import k1_kernel as kk

    def fn(pkx, u1, u2, r, rpn, parity):
        return kk.k1_verify_compact_kernel(
            pkx, parity, u1, u2, r, rpn, tile=TILE, interpret=False)

    _compile_single(v5e, fn, 5, extra=(((TILE,), jnp.int32),))


def test_sharded_tally_step_compiles_for_the_four_chip_mesh(v5e):
    """The production mesh entry (tpu/mesh_dispatch.py ed25519 tally):
    the fused kernel under shard_map over all four chips at the 10k
    VoteSet's padded width, one all-reduce for the power tally."""
    from tmtpu.tpu import sharding as sh

    mesh = Mesh(np.asarray(v5e.devices), ("sig",))
    lanes = 10_240
    step = sh.sharded_verify_tally_packed_kernel(mesh, tile=TILE,
                                                 interpret=False)
    lane = NamedSharding(mesh, P(None, "sig"))
    compiled = step.lower(
        jax.ShapeDtypeStruct((128, lanes), jnp.uint8, sharding=lane),
        jax.ShapeDtypeStruct((sh.POWER_LIMBS, lanes), jnp.int32,
                             sharding=lane)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert text.count("all-reduce(") + text.count("all-reduce-start(") == 1
