"""Ahead-of-time compiles for one TPU v5e chip that is described, not
attached: the programs of the committing peer and the Pallas kernels at
real widths (``PAPER_DIMS``, 100-tx blocks, a 2^20-bucket table for the
step programs, the largest table the hash-table kernel's VMEM budget
holds).

The TPU compiler refuses what interpret-mode tests cannot see: misaligned
blocks, scalars in vector memory, programs that do not fit the chip. These
tests raise what it would raise. Nothing runs, so they say nothing about
results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the fixture skips
where it cannot be loaded. Keep every such compile in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import committer, engine, types
from repro.kernels.hash_table import kernel as htk
from repro.kernels.hash_table import ops as ht_ops
from repro.kernels.mvcc_validate import kernel as mvk
from repro.kernels.sig_mac import kernel as smk
from repro.launch import fabric_step as fs
from repro.launch.mesh import make_mesh

DIMS = types.PAPER_DIMS
N_BUCKETS = 1 << 20
BLOCK = 100
HBM_BYTES = 16 * 10**9  # one v5e chip
U32 = jnp.uint32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _fits_chip(compiled):
    m = compiled.memory_analysis()
    live = (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)
    print(m)
    assert live < HBM_BYTES, m


def test_window_step_depth8_compiles(topo, one_chip):
    """The depth-8 window step as the window committer jits it (state
    donated), on a 1x1 mesh of the described chip."""
    mesh = make_mesh((1, 1), devices=[topo.devices[0]])
    step = jax.jit(fs.make_fabric_step(
        DIMS, fs.FabricStepConfig(pipeline_depth=8), mesh),
        donate_argnums=(0,))
    state = _on(one_chip, jax.eval_shape(
        lambda: fs.create_mesh_state(1, DIMS, n_buckets=N_BUCKETS)))
    wire, ids = _on(one_chip, fs.input_specs(mesh, DIMS, b_loc=BLOCK,
                                             pipeline_depth=8))
    compiled = step.lower(state, wire, ids).compile()
    _fits_chip(compiled)
    assert compiled.memory_analysis().alias_size_in_bytes > 0  # donated


def test_host_commit_block_compiles(one_chip):
    """The host path's per-block commit (P-III fused program)."""
    state = _on(one_chip, jax.eval_shape(
        lambda: committer.create_peer_state(DIMS, n_buckets=N_BUCKETS)))
    wire = jax.ShapeDtypeStruct((BLOCK, 4 * DIMS.payload_words), jnp.uint8,
                                sharding=one_chip)
    compiled = committer.commit_block_fused.lower(
        state, wire, DIMS, engine.FASTFABRIC.peer).compile()
    _fits_chip(compiled)


def _kernel_args(name, s):
    rk, wk, vw = DIMS.rk, DIMS.wk, DIMS.vw
    if name in ("hash_lookup", "hash_commit"):
        slots, nb = 8, 1
        while htk.vmem_bytes(2 * nb, slots, vw) <= ht_ops.VMEM_BUDGET_BYTES:
            nb *= 2  # the largest table the kernel's VMEM budget holds
        table = (s((nb, slots, 2)), s((nb, slots)), s((nb, slots, vw)))
        if name == "hash_lookup":
            return htk.lookup, (*table, s((BLOCK * rk, 2)))
        return htk.commit, (*table, s((BLOCK * wk, 2)), s((BLOCK * wk, vw)),
                            s((BLOCK * wk,), jnp.bool_))
    if name == "mvcc_validate":
        nb = 8  # one depth-8 window of blocks
        return mvk.validate_blocks, (
            s((nb, BLOCK, rk, 2)), s((nb, BLOCK, rk)), s((nb, BLOCK, wk, 2)),
            s((nb, BLOCK, rk)), s((nb, BLOCK), jnp.bool_))
    msg_words = DIMS.struct_words - DIMS.ne  # what the tags sign
    return smk.mac_many, (s((BLOCK, msg_words)), s((DIMS.ne,)),
                          s((DIMS.ne,)))


@pytest.mark.parametrize("name", ["hash_lookup", "hash_commit",
                                  "mvcc_validate", "sig_mac"])
def test_kernel_compiles_for_chip(one_chip, name):
    """Each Pallas kernel compiles to a Mosaic custom call at real widths."""
    s = lambda shape, dt=U32: jax.ShapeDtypeStruct(shape, dt,
                                                   sharding=one_chip)
    fn, args = _kernel_args(name, s)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
