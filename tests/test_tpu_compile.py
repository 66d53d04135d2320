"""The KGE path's Pallas kernels compile for a TPU v5e, without a chip.

Each kernel that ``kernels/ops.py`` dispatches on the main path is lowered
and compiled by the TPU compiler for a described ``v5e:2x2`` topology at
D=256: the round-table kernels at a flattened ``(C * Ns_max, D)`` shape,
the eval kernels at the evaluator's ``(B, D) x (chunk, D)`` tiles (and
under its client ``vmap``, TransE's MXU tiles also at a whole R3 test
split and at D=1024, which checks their VMEM rule), and each round kernel once more inside a
``shard_map`` over the four chips, as the pod engines call it.  A passing
compile is not a chip run: it checks only that Mosaic accepts the kernel
and that the compiled program calls it (``tpu_custom_call``).

The topology is described inside a fixture, never at import, so every
pytest-xdist worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.bilinear_score import bilinear_cand_score_pallas
from repro.kernels.change_score import change_score_pallas
from repro.kernels.kge_score import dist_cand_score_pallas
from repro.kernels.sparse_apply import sparse_apply_pallas

D = 256
N_ROUND = 15104  # a flattened (C * Ns_max, D) round table
B, CHUNK, C = 512, 512, 3  # eval query block, candidate chunk, clients
B_EVAL = 17256  # the largest client's test split, R3 (the evaluator's B_max)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return jax.sharding.Mesh(np.array(topo.devices), ("clients",))


def _dist(method):
    return lambda q, c: dist_cand_score_pallas(q, c, 8.0, method=method)


# name -> (function, argument shapes and dtypes)
SINGLE = {
    "change_score": (change_score_pallas,
                     [((N_ROUND, D), jnp.float32)] * 2),
    "sparse_apply": (sparse_apply_pallas,
                     [((N_ROUND, D), jnp.float32)] * 2
                     + [((N_ROUND,), jnp.float32), ((N_ROUND,), jnp.int8)]),
    **{
        f"dist_cand_score_{m}": (_dist(m), [((B, D), jnp.float32),
                                            ((CHUNK, D), jnp.float32)])
        for m in ("transe", "rotate", "protate")
    },
    "bilinear_cand_score": (bilinear_cand_score_pallas,
                            [((B, D), jnp.float32), ((CHUNK, D), jnp.float32)]),
    # the evaluator maps the eval kernels over the client axis
    "dist_cand_score_transe_vmap": (
        jax.vmap(_dist("transe")),
        [((C, B, D), jnp.float32), ((C, CHUNK, D), jnp.float32)],
    ),
    "bilinear_cand_score_vmap": (
        jax.vmap(bilinear_cand_score_pallas),
        [((C, B, D), jnp.float32), ((C, CHUNK, D), jnp.float32)],
    ),
    # TransE's MXU tiles at the R3 evaluator's whole test split, and wide
    **{
        f"dist_cand_score_transe_vmap_eval_d{d}": (
            jax.vmap(_dist("transe")),
            [((C, B_EVAL, d), jnp.float32), ((C, CHUNK, d), jnp.float32)],
        )
        for d in (D, 1024)
    },
}

# kernels as the pod engines call them: per-shard, inside a shard_map
SHARDED = {
    "change_score": (change_score_pallas, 2, 0),
    "sparse_apply": (sparse_apply_pallas, 2, 2),
    "dist_cand_score_transe": (_dist("transe"), 2, 0),
    "bilinear_cand_score": (bilinear_cand_score_pallas, 2, 0),
}


def _compiled_text(fn, args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("name", sorted(SINGLE))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = SINGLE[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    assert "tpu_custom_call" in _compiled_text(fn, args)


@pytest.mark.parametrize("name", sorted(SHARDED))
def test_kernel_compiles_under_shard_map_for_v5e(name, mesh):
    fn, n_tables, n_vectors = SHARDED[name]
    n = 4 * B  # four clients' blocks, one per chip
    spec = P("clients")
    shard = NamedSharding(mesh, spec)
    args = (
        [jax.ShapeDtypeStruct((n, D), jnp.float32, sharding=shard)] * n_tables
        + [jax.ShapeDtypeStruct((n,), jnp.float32, sharding=shard)]
        * n_vectors
    )
    body = jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * len(args),
                         out_specs=spec)
    assert "tpu_custom_call" in _compiled_text(body, args)
