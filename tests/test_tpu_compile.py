"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* ``v5e:2x2`` topology and refuses what the chip would
refuse (unaligned Mosaic slices, VMEM overruns, programs that do not fit).
Shapes are those of the scale-20 R-MAT deployment (``chip_smoke.py``:
``rmat(20, 16, seed=1)``, k=32) as the LP engine buckets them.  Nothing
runs; each case only lowers and compiles one executable.
"""

import functools
import os

import pytest

import jax
import jax.numpy as jnp

from repro.core.contraction import contract_device, packed_key_wbits
from repro.core.evo_device import evo_generation_step, evo_seed_step
from repro.core.label_propagation import _lp_sweep
from repro.dynamic.repair import expand_region_device, gain_round_device
from repro.dynamic.store import merge_overlay_device
from repro.graph.csr import arc_bucket, pow2
from repro.graph.packing import chunk_geometry, gather_pack_device
from repro.kernels.lp_score import lp_score_rows, pad_k

# rmat(20, 16, seed=1): node and arc counts of the generated graph
N_NODES = 1 << 20
M_ARCS = 31_404_556
K = 32
A = pow2(N_NODES + 1)                 # label arena
NB = pow2(N_NODES)                    # node bucket of the CSR
MB = arc_bucket(M_ARCS)               # arc bucket of the CSR
_n_req, _e_req = chunk_geometry(N_NODES, M_ARCS, 64)
CHUNK_N = pow2(_n_req)                # nodes per chunk
CHUNK_E = -(-_e_req // 512) * 512     # arcs per chunk (512-arc rungs)
CHUNKS = 128                          # chunk bucket of the finest pack
COARSE_N = N_NODES // 8               # coarsest target: n // 8
COARSE_AB = pow2(COARSE_N + 1)
COARSE_MB = arc_bucket(M_ARCS // 8)
ELL_ROWS = pow2(N_NODES + M_ARCS // 128)
OVERLAY = 4096                        # one churn batch: 2 x 1,024 edges
# the benchmark's rgg-mesh (scale 15, k=32): the GA runs on the whole mesh
# and reads its neighbour table (n rounded to 256 rows, max degree 27 -> 32)
MESH_N, MESH_M = 1 << 15, 322_340
MESH_AB = pow2(MESH_N + 1)
_mesh_n, _mesh_e = chunk_geometry(MESH_N, MESH_M, 64)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> ShapeDtypeStruct placed on one v5e chip."""
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    # compiles for a described chip are written to the persistent cache
    # but cannot be read back without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda dims, dtype: jax.ShapeDtypeStruct(
        tuple(dims), dtype, sharding=one
    )
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


I32, F32, BOOL, U32 = jnp.int32, jnp.float32, jnp.bool_, jnp.uint32


def _scalar(shape, dtype):
    return shape((), dtype)


def _pack(shape, C):
    return (
        shape((C, CHUNK_N), I32), shape((C, CHUNK_N), BOOL),
        shape((C, CHUNK_E), I32), shape((C, CHUNK_E), F32),
        shape((C, CHUNK_E), I32), shape((C, CHUNK_E), BOOL),
    )


def _sweep(shape):
    """The refine-mode sweep: the cluster mode runs the same sort-based run
    reduction (the (slot, label) key needs two sort keys at this arena)."""
    args = _pack(shape, CHUNKS) + (
        shape((A,), I32), shape((K + 1,), F32), shape((A,), F32),
        shape((1,), I32), _scalar(shape, F32), _scalar(shape, I32),
        _scalar(shape, I32), _scalar(shape, I32),
    )
    return _lp_sweep.lower(
        *args, iters=6, refine_mode=True, use_restrict=False,
        permute_chunks=True,
    )


def _contract(shape):
    wbits = packed_key_wbits(NB, MB, 1.0, True)
    return contract_device.lower(
        shape((MB,), I32), shape((MB,), I32), shape((MB,), F32),
        shape((NB,), F32), shape((NB,), I32),
        _scalar(shape, I32), _scalar(shape, I32), wbits=wbits,
    )


def _gather(shape):
    return gather_pack_device.lower(
        shape((CHUNKS, CHUNK_N), I32), shape((CHUNKS, CHUNK_N), BOOL),
        shape((NB + 1,), I32), shape((MB,), I32), shape((MB,), F32),
        _scalar(shape, I32), E=CHUNK_E,
    )


def _merge(shape):
    return merge_overlay_device.lower(
        shape((MB,), I32), shape((MB,), I32), shape((MB,), F32),
        shape((OVERLAY,), I32), shape((OVERLAY,), I32),
        shape((OVERLAY,), F32), shape((NB,), F32),
        _scalar(shape, I32), _scalar(shape, I32), _scalar(shape, I32),
    )


def _expand(shape):
    return expand_region_device.lower(
        shape((8192,), I32), shape((MB,), I32), shape((MB,), I32),
        shape((NB + 1,), I32), _scalar(shape, I32), _scalar(shape, I32),
        _scalar(shape, I32), A=A,
    )


def _gain(shape):
    return gain_round_device.lower(
        shape((MB,), I32), shape((MB,), I32), shape((MB,), F32),
        shape((A,), F32), shape((A,), I32), shape((A,), BOOL),
        _scalar(shape, I32), _scalar(shape, I32), _scalar(shape, F32),
        _scalar(shape, U32), _scalar(shape, U32), Kb=K + 1,
    )


def _evo(shape):
    Sb, Ib, Kb = 4, 2, pow2(K + 1)
    args = _pack(shape, 16) + (
        shape((Sb, COARSE_AB), I32), shape((Sb,), I32),
        shape((COARSE_MB,), I32), shape((COARSE_MB,), I32),
        shape((COARSE_MB,), F32), shape((COARSE_AB,), F32),
        _scalar(shape, F32), _scalar(shape, I32), _scalar(shape, I32),
        _scalar(shape, I32),
    ) + tuple(_scalar(shape, I32) for _ in range(5))
    return evo_generation_step.lower(*args, refine_iters=6, Kb=Kb, Ib=Ib)


def _evo_seed_table(shape):
    Sb, Kb, W = 4, pow2(K + 1), 32
    C, Nc, Ec = 64, pow2(_mesh_n), -(-_mesh_e // 512) * 512
    args = (
        shape((C, Nc), I32), shape((C, Nc), BOOL), shape((C, Ec), I32),
        shape((C, Ec), F32), shape((C, Ec), I32), shape((C, Ec), BOOL),
        shape((Sb, MESH_AB), I32), shape((Sb,), BOOL),
        shape((MESH_M,), I32), shape((MESH_M,), I32), shape((MESH_M,), F32),
        shape((MESH_AB,), F32), shape((MESH_AB,), F32),
        _scalar(shape, F32), _scalar(shape, I32),
    ) + tuple(_scalar(shape, I32) for _ in range(6))
    table = (shape((MESH_N, W), I32), shape((MESH_N, W), F32))
    return evo_seed_step.lower(*args, table, refine_iters=6, Kb=Kb)


def _lp_score(shape, width):
    return lp_score_rows.lower(
        shape((ELL_ROWS, width), I32), shape((ELL_ROWS, width), F32),
        k_pad=pad_k(K), interpret=False,
    )


KERNELS = {
    "lp_sweep": _sweep,
    "contract_device": _contract,
    "gather_pack_device": _gather,
    "merge_overlay_device": _merge,
    "expand_region_device": _expand,
    "gain_round_device": _gain,
    "evo_generation_step": _evo,
    "evo_seed_step_table": _evo_seed_table,
    "lp_score_rows_w8": functools.partial(_lp_score, width=8),
    "lp_score_rows_w64": functools.partial(_lp_score, width=64),
    "lp_score_rows_w128": functools.partial(_lp_score, width=128),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_compiles_for_v5e(shape, name):
    compiled = KERNELS[name](shape).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 2**30, f"{name} needs {used} bytes of HBM"
    if name.startswith("lp_score_rows"):
        assert "tpu_custom_call" in compiled.as_text()
