"""Pallas TPU kernel: fused DynLP frontier propagation step (Alg. 2 L23-32).

The paper's CUDA version assigns a thread block per CSR row and reduces
partial edge sums in shared memory (Fig. 3).  The TPU formulation processes
ELL row *tiles*: a (R, K) block of neighbor ids/weights per grid step, the
full label vector F resident in VMEM (per-shard N ≤ ~4M floats ≪ 16 MiB),
and the whole update — gather, weighted average, δ-threshold, frontier
decision — fused into one VPU pass so F is read from HBM once per sweep.

Grid: (N // R,).  BlockSpecs tile nbr/wgt/wl0/wl1/frontier by rows; F and
the output F' use a constant index_map (whole-vector VMEM residency).

out[0] = F'        (N,)  updated labels (only frontier rows move)
out[1] = changed   (N,)  |ΔF| > δ flags (drives the next frontier)

Mosaic does not lower this kernel: the per-edge read ``F[nbr]`` is a 1-D
dynamic gather from VMEM, and the TPU compiler refuses it ("Only 2D gather
is supported").  It runs interpreted off-TPU only, and the backend registry
never auto-selects it (``kernels.ops``); making it compile needs a
different gather strategy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret


def _kernel(nbr_ref, wgt_ref, wl0_ref, wl1_ref, frontier_ref, f_ref,
            delta_ref, offset_ref, fout_ref, changed_ref):
    nbr = nbr_ref[...]  # (R, K) int32
    wgt = wgt_ref[...]  # (R, K) f32
    f_all = f_ref[...]  # (N,) f32 — VMEM resident
    # offset maps this invocation's row tile into F: 0 single-device, the
    # shard's global row base under shard_map (core.distributed).
    row0 = pl.program_id(0) * nbr.shape[0] + offset_ref[0]
    rows = row0 + jax.lax.iota(jnp.int32, nbr.shape[0])
    # clamp: a shard whose row block is padded past a multiple of R may
    # point its pad rows beyond F — their outputs are discarded anyway
    rows = jnp.minimum(rows, f_all.shape[0] - 1)
    f_u = f_all[rows]  # (R,)

    mask = nbr >= 0
    idx = jnp.where(mask, nbr, 0)
    f_v = jnp.take(f_all, idx.reshape(-1), axis=0).reshape(idx.shape)
    nbr_term = jnp.sum(wgt * jnp.where(mask, f_v - f_u[:, None], 0.0), axis=1)

    wl0 = wl0_ref[...]
    wl1 = wl1_ref[...]
    wall = jnp.sum(wgt, axis=1) + wl0 + wl1
    delta_f = (0.0 - f_u) * wl0 + (1.0 - f_u) * wl1 + nbr_term
    f_new = f_u + jnp.where(wall > 0, delta_f / jnp.maximum(wall, 1e-30), 0.0)

    frontier = frontier_ref[...]
    f_new = jnp.where(frontier, f_new, f_u)
    fout_ref[...] = f_new
    changed_ref[...] = jnp.abs(f_new - f_u) > delta_ref[0]


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ell_propagate_step(
    nbr: jax.Array,  # (N, K) int32, PAD == -1
    wgt: jax.Array,  # (N, K) float32
    wl0: jax.Array,  # (N,)
    wl1: jax.Array,  # (N,)
    frontier: jax.Array,  # (N,) bool
    f: jax.Array,  # (Nf,) float32 — Nf ≥ N; the gathered GLOBAL labels
    delta: float = 1e-4,
    block_rows: int = 512,
    interpret: bool | None = None,
    row_offset: jax.Array | int = 0,
) -> tuple[jax.Array, jax.Array]:
    """One fused frontier sweep over ``nbr``'s rows.

    Single-device callers pass ``f`` of the same length as ``nbr`` and
    ``row_offset=0``.  Under ``shard_map`` (core.distributed) ``nbr`` is
    the shard's row block, ``f`` the all-gathered global vector, and
    ``row_offset`` the shard's global row base — outputs stay per-shard.
    """
    n, k = nbr.shape
    n_f = f.shape[0]
    r = min(block_rows, n)
    assert n % r == 0, (n, r)
    grid = (n // r,)
    delta_arr = jnp.full((1,), delta, jnp.float32)
    offset_arr = jnp.full((1,), row_offset, jnp.int32)
    row_spec = lambda width=None: pl.BlockSpec(
        (r,) if width is None else (r, width), lambda i: (i,) if width is None else (i, 0)
    )
    full_spec = pl.BlockSpec((n_f,), lambda i: (0,))
    scalar_spec = pl.BlockSpec((1,), lambda i: (0,))
    fout, changed = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            row_spec(k),  # nbr
            row_spec(k),  # wgt
            row_spec(),  # wl0
            row_spec(),  # wl1
            row_spec(),  # frontier
            full_spec,  # f (whole vector in VMEM)
            scalar_spec,  # delta
            scalar_spec,  # row offset
        ],
        out_specs=[row_spec(), row_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.float32),
            jax.ShapeDtypeStruct((n,), jnp.bool_),
        ],
        interpret=resolve_interpret(interpret),
    )(nbr, wgt, wl0.astype(jnp.float32), wl1.astype(jnp.float32),
      frontier, f.astype(jnp.float32), delta_arr, offset_arr)
    return fout, changed
