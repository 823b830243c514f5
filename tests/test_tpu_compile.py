"""Compile the main path's device programs for a described TPU v5e.

Nothing here runs on a chip: each test lowers a kernel or jitted solve
against the devices of a described ``v5e:2x2`` topology and compiles it
with the installed TPU compiler, which refuses what interpret mode cannot
catch (layouts Mosaic does not lower, unaligned blocks, VMEM overruns).
Shapes are the deployment's: an ogbn-arxiv-sized stream (169,343 rows of
128-d embeddings, 78,402 of them unlabelled) ingested in 1,024-row
windows with k=5.

The topology is described inside a module fixture, never at import time:
only one process may hold the TPU library, and every test worker imports
this file.  The same file checks that the TPU auto scan of the backend
registry returns only backends compiled here.
"""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import distributed
from repro.core.propagate import PropagationProblem, propagate
from repro.core.snapshot import bucket, bucket_k
from repro.graph.knn import SELECT_MARGIN
from repro.ingest.embedding_store import batch_bucket, cap_bucket, dim_pad
from repro.kernels import argkmin, ops
from repro.kernels.bsr_spmv import bsr_spmv
from repro.kernels.ell_propagate import ell_propagate_step
from repro.kernels.landmark_propagate import LandmarkConfig, _cold_pass

ROWS, DIM, K, WINDOW = 169_343, 128, 5, 1024
UNLABELLED = ROWS - 90_941
BS = 128  # the bsr tile edge on TPU
# the padded unlabelled rung and neighbor width the engine stages
U_PAD = -BS * (-bucket(UNLABELLED) // BS)
K_PAD = bucket_k(4 * K)  # StreamEngine's default max_k = 4k

# what the TPU auto scan may return; each is compiled below
COMPILED = ("ref", "bsr", "landmark")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile can be written to the persistent cache
    # but never read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _problem(sh2, sh1, u, k):
    return PropagationProblem(
        nbr=_spec((u, k), jnp.int32, sh2), wgt=_spec((u, k), jnp.float32, sh2),
        wl0=_spec((u,), jnp.float32, sh1), wl1=_spec((u,), jnp.float32, sh1),
        valid=_spec((u,), jnp.bool_, sh1))


def _compiled_text(jitted, *args, **kw) -> str:
    return jitted.lower(*args, **kw).compile().as_text()


@pytest.mark.parametrize("cap", [cap_bucket(1), cap_bucket(ROWS)])
def test_argkmin_pallas_compiles(one_chip, cap):
    """The ingest argkmin kernel at the store ladder's floor and top rung,
    one admission window of 128-d rows."""
    m, d = batch_bucket(WINDOW), dim_pad(DIM)
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    txt = _compiled_text(
        argkmin._argkmin_pallas,
        s((cap, d), jnp.float32), s((cap,), jnp.bool_),
        s((cap,), jnp.float32), s((m, d), jnp.float32), s((m,), jnp.bool_),
        s((), jnp.int32), s((), jnp.float32), s((), jnp.int32),
        topk=K + SELECT_MARGIN, block_rows=256, interpret=False)
    assert "tpu_custom_call" in txt


def test_bsr_spmv_compiles(one_chip):
    """The MXU SpMV at its 128 tile edge."""
    r, j = 512, 8
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    txt = _compiled_text(bsr_spmv, s((r, j, BS, BS), jnp.float32),
                         s((r, j), jnp.int32), s((r * BS,), jnp.float32),
                         interpret=False)
    assert "tpu_custom_call" in txt


def test_ref_solve_compiles_at_deployment_rung(one_chip):
    """``ref`` — what auto resolves for the deployment's sparse rungs —
    donating stream form and plain form, at the padded (U, K)."""
    prob = _problem(one_chip, one_chip, U_PAD, K_PAD)
    f0 = _spec((U_PAD,), jnp.float32, one_chip)
    fr = _spec((U_PAD,), jnp.bool_, one_chip)
    _compiled_text(ops._ref_donating, prob, f0, fr, 1e-4, 200_000)
    _compiled_text(propagate, prob, f0, fr, 1e-4, max_iters=200_000)


def test_bsr_solve_compiles(one_chip):
    """``bsr`` — what auto resolves for a dense-tiled rung — with the
    tile fill inside the jitted solve."""
    u, slots = 8 * 1024, 8
    prob = _problem(one_chip, one_chip, u, K_PAD)
    txt = _compiled_text(
        ops._bsr_donating, prob, _spec((u, K_PAD), jnp.int32, one_chip),
        _spec((u,), jnp.float32, one_chip), _spec((u,), jnp.bool_, one_chip),
        1e-4, max_iters=200_000, interpret=False, block_size=BS,
        num_slots=slots)
    assert "tpu_custom_call" in txt


def test_landmark_cold_pass_compiles(one_chip):
    """``landmark`` solves with the ref body (compiled above) and serves
    the cold tail with this pass."""
    cfg = LandmarkConfig()
    n = cap_bucket(ROWS)
    _compiled_text(_cold_pass,
                   _spec((n, cfg.assign_k), jnp.int32, one_chip),
                   _spec((n, cfg.assign_k), jnp.float32, one_chip),
                   _spec((cfg.num_landmarks,), jnp.float32, one_chip))


def test_ell_pallas_is_refused_by_mosaic(one_chip):
    """Why ``ell_pallas`` is out of the TPU auto scan: its in-kernel
    gather of F by neighbor id does not lower.  If this ever compiles,
    the auto rule in ``kernels.ops`` can be revisited."""
    n = 4096
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    with pytest.raises(Exception, match="gather"):
        _compiled_text(ell_propagate_step, s((n, K_PAD), jnp.int32),
                       s((n, K_PAD), jnp.float32), s((n,), jnp.float32),
                       s((n,), jnp.float32), s((n,), jnp.bool_),
                       s((n,), jnp.float32), interpret=False)


def test_four_chip_store_sweep_and_solve_compile(topo):
    """The row-sharded paths of a four-chip host: the move-the-batch
    argkmin sweep over a sharded store, and the all-gather ref solve."""
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    row, row2, rep = (NamedSharding(mesh, P("data")),
                      NamedSharding(mesh, P("data", None)),
                      NamedSharding(mesh, P()))
    cap, m, d = cap_bucket(ROWS), batch_bucket(WINDOW), dim_pad(DIM)
    _, sweep = distributed._store_sweep_for(
        mesh, backend="pallas", block_rows=256, interpret=False)
    txt = _compiled_text(
        sweep, _spec((cap, d), jnp.float32, row2),
        _spec((cap,), jnp.bool_, row), _spec((cap,), jnp.float32, row),
        _spec((m, d), jnp.float32, rep), _spec((m,), jnp.bool_, rep),
        _spec((), jnp.int32, rep), _spec((), jnp.float32, rep),
        topk=K + SELECT_MARGIN)
    assert "tpu_custom_call" in txt and "all-gather" in txt
    u = -(4 * BS) * (-bucket(UNLABELLED) // (4 * BS))
    solve = distributed.make_sharded_propagate_fn(mesh, backend="ref",
                                                  donate=True)
    _compiled_text(solve, _spec((u, K_PAD), jnp.int32, row2),
                   _spec((u, K_PAD), jnp.float32, row2),
                   _spec((u,), jnp.float32, row), _spec((u,), jnp.float32, row),
                   _spec((u,), jnp.bool_, row), _spec((u,), jnp.float32, row),
                   _spec((u,), jnp.bool_, row))


def test_tpu_auto_scan_returns_only_compiled_backends():
    """For every rung shape the ladder produces up to the deployment, and
    every fill / landmark / mesh state, the TPU auto scan lands on a
    backend whose programs this file compiles."""
    rungs, b = [], 256
    while b <= U_PAD:
        rungs.append(b)
        b = bucket(b + 1)
    fills = (None, 0.0, 1 / 128, ops.bsr_auto_fill_min("tpu"), 1.0)
    for u, fill, lm, sharded in itertools.product(
            rungs, fills, (False, True), (False, True)):
        info = ops.ProblemInfo(num_rows=u, block_fill=fill, sharded=sharded,
                               landmark_ready=lm)
        assert ops._auto_select(info, "tpu") in COMPILED, info
    assert not ops.backend_spec("ell_pallas").auto_eligible(
        ops.ProblemInfo(num_rows=U_PAD), "tpu")
