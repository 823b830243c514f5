"""Pallas TPU kernel: block-sparse SpMV with scalar-prefetched block indices.

This is the MXU path for DynLP's aggregation on *reordered* graphs: after
clustering vertices by connected component (Step 1 produces exactly this
ordering), the adjacency matrix densifies into blocks; storing it as
row-padded BSR (each block row has J tile slots, empty slots flagged -1)
turns the irregular SpMV of the paper into a sequence of dense
(BS × BS) @ (BS,) MXU ops.

The block-column ids are SCALAR-PREFETCHED: the x BlockSpec's index_map
reads them to decide which x tile to stage into VMEM before each grid step
— the canonical Pallas TPU sparse pattern (no dynamic gathers in the body).

The BSR form is built **directly from the ELL tensor** — never through a
dense (U, U) intermediate:

  * ``ell_bsr_layout`` (host, O(nnz log nnz)) assigns every ELL edge a
    slot inside its block row and reports the layout's slot requirement
    and block fill factor;
  * ``fill_bsr_blocks`` (device, O(nnz) scatter, runs inside the jitted
    solve) turns the staged ELL ``(nbr, wgt)`` plus the slot map into the
    ``(R, J, BS, BS)`` tile tensor and ``(R, J)`` block-column ids.

The slot map is the only extra array shipped per Δ_t (same shape as
``nbr``); the tiles themselves only ever exist on the device.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret


def _kernel(cols_ref, blocks_ref, x_ref, y_ref):
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(cols_ref[i, j] >= 0)
    def _acc():
        a = blocks_ref[0, 0].astype(jnp.float32)  # (BS, BS)
        # x is one (1, BS) lane row; broadcast it over a full sublane tile
        # so the MXU sees an aligned (8, BS) x (BS, BS)^T product, and keep
        # row 0: y[u] = sum_v a[u, v] * x[v]
        x = jnp.broadcast_to(x_ref[...].astype(jnp.float32), (8, a.shape[1]))
        y = jax.lax.dot_general(x, a, (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        y_ref[...] += y[:1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bsr_spmv(
    blocks: jax.Array,  # (R, J, BS, BS) float — row-padded BSR tiles
    block_cols: jax.Array,  # (R, J) int32 — tile column ids, -1 = empty
    x: jax.Array,  # (C * BS,) float
    interpret: bool | None = None,
) -> jax.Array:
    """Block-sparse y = A @ x over `(R, J, BS, BS)` BSR tiles on the MXU.

    Empty tile slots carry `block_cols == -1` and are steered to a
    zero-weight read of column block 0, so padding never contributes.
    ``x`` and ``y`` travel as (1, N) rows so every block is a lane-aligned
    (1, BS) slice; the dot runs in full f32 (``Precision.HIGHEST``) so the
    MXU path stays allclose to the XLA reference.
    """
    r, j, bs, _ = blocks.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(r, j),
        in_specs=[
            pl.BlockSpec((1, 1, bs, bs), lambda i, jj, cols: (i, jj, 0, 0)),
            pl.BlockSpec((1, bs),
                         lambda i, jj, cols: (0, jnp.maximum(cols[i, jj], 0))),
        ],
        out_specs=pl.BlockSpec((1, bs), lambda i, jj, cols: (0, i)),
    )
    y = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, r * bs), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(block_cols, blocks, x.reshape(1, -1))
    return y[0]


# --------------------------------------------------------------------- #
# Direct ELL -> BSR build (no dense intermediate)
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class BsrLayout:
    """Host-side slot assignment for one ELL snapshot.

    ``slot[u, k]`` is the tile slot (within block row ``u // block_size``)
    that edge ``(u, nbr[u, k])`` scatters into, or -1 for empty ELL lanes.
    ``num_slots`` is the layout's exact requirement (max distinct block
    columns touched by any block row); callers compile for a padded budget
    ≥ it and fall back when a later snapshot exceeds the budget.
    """

    slot: np.ndarray  # (U_pad, K) int32, -1 on empty lanes
    num_slots: int  # max distinct block cols in any block row (≥ 1)
    n_blocks: int  # distinct (block row, block col) pairs with an edge
    nnz: int  # real ELL edges
    block_size: int

    @property
    def fill(self) -> float:
        """Fraction of the touched tiles' entries that carry an edge —
        the density the MXU actually computes on.  1.0 means every
        touched (BS, BS) tile is completely dense."""
        cap = self.n_blocks * self.block_size * self.block_size
        return self.nnz / cap if cap else 0.0


def ell_bsr_layout(nbr: np.ndarray, block_size: int) -> BsrLayout:
    """Assign every ELL edge a BSR tile slot — host, O(nnz log nnz).

    Rows are expected pre-ordered (component order or halo layout); the
    layout never reorders.  ``len(nbr)`` must be a multiple of
    ``block_size`` (callers pad rows first).
    """
    m, _ = nbr.shape
    if m % block_size:
        raise ValueError(f"rows {m} not a multiple of block_size {block_size}")
    valid = nbr >= 0
    nnz = int(valid.sum())
    r = m // block_size
    if nnz == 0:
        return BsrLayout(slot=np.full(nbr.shape, -1, np.int32), num_slots=1,
                         n_blocks=0, nnz=0, block_size=block_size)
    br = np.repeat(np.arange(r, dtype=np.int64), block_size)[:, None]
    n_cols = int(nbr.max()) // block_size + 1
    # one key per (block row, block col) pair; rank each row's distinct
    # pairs by searchsorted into the global sorted-unique key list
    key = np.where(valid, br * n_cols + nbr // block_size, -1)
    uniq = np.unique(key[valid])
    pos = np.searchsorted(uniq, key)
    seg = np.searchsorted(uniq // n_cols, np.arange(r, dtype=np.int64))
    slot = np.where(valid, pos - seg[br], -1).astype(np.int32)
    counts = np.diff(np.append(seg, len(uniq)))
    return BsrLayout(slot=slot, num_slots=int(max(1, counts.max())),
                     n_blocks=len(uniq), nnz=nnz, block_size=block_size)


def fill_bsr_blocks(nbr: jax.Array, wgt: jax.Array, slot: jax.Array,
                    *, block_size: int, num_slots: int):
    """Device-side O(nnz) scatter: staged ELL rows -> row-padded BSR.

    Traced inside the jitted solves (single-device ``_bsr_solve`` and the
    sharded update bodies), so the (R, J, BS, BS) tile tensor never
    exists on the host.  ``nbr`` may hold *global* column ids (sharded
    path) — block columns index whatever vector the SpMV later consumes.
    Lanes whose slot falls outside ``[0, num_slots)`` are DROPPED, never
    scattered (an out-of-budget slot would otherwise land in a
    neighboring block row's tile); callers guarantee the budget covers
    the layout (``propagate_bsr`` validates host-side slot maps, the
    streaming engine checks its per-rung budget before dispatch).
    Returns ``(blocks, block_cols)`` for ``bsr_spmv``.
    """
    m, _ = nbr.shape
    r = m // block_size
    rows = jax.lax.broadcasted_iota(jnp.int32, nbr.shape, 0)
    br = rows // block_size
    ur = rows % block_size
    valid = (nbr >= 0) & (slot >= 0) & (slot < num_slots)
    s = jnp.where(valid, slot, 0)
    vc = jnp.where(valid, nbr % block_size, 0)
    flat = ((br * num_slots + s) * block_size + ur) * block_size + vc
    # every real ELL edge owns a distinct target (rows list each neighbor
    # once); invalid lanes alias slot 0 but contribute an exact 0.0
    blocks = jnp.zeros((r * num_slots * block_size * block_size,), jnp.float32)
    blocks = blocks.at[flat.reshape(-1)].add(
        jnp.where(valid, wgt, 0.0).astype(jnp.float32).reshape(-1))
    bc = jnp.where(valid, nbr // block_size, -1)
    cols = jnp.full((r, num_slots), -1, jnp.int32)
    cols = cols.at[br.reshape(-1), s.reshape(-1)].max(bc.reshape(-1))
    return blocks.reshape(r, num_slots, block_size, block_size), cols


def dense_to_bsr(a: jax.Array, bs: int):
    """Dense (N, M) -> row-padded BSR (blocks, block_cols).

    .. deprecated:: kept as the *test oracle* for ``ell_bsr_layout`` /
       ``fill_bsr_blocks`` only.  Production paths build BSR directly
       from the ELL tensor (O(nnz), no dense intermediate) — do not use
       this on any hot path.
    """
    a = np.asarray(a)
    n, m = a.shape
    assert n % bs == 0 and m % bs == 0
    rb, cb = n // bs, m // bs
    tiles = a.reshape(rb, bs, cb, bs).transpose(0, 2, 1, 3)  # (rb, cb, bs, bs)
    nz = np.array([[tiles[i, j].any() for j in range(cb)] for i in range(rb)])
    jmax = max(1, int(nz.sum(1).max()))
    blocks = np.zeros((rb, jmax, bs, bs), a.dtype)
    cols = np.full((rb, jmax), -1, np.int32)
    for i in range(rb):
        slot = 0
        for j in range(cb):
            if nz[i, j]:
                blocks[i, slot] = tiles[i, j]
                cols[i, slot] = j
                slot += 1
    return jnp.asarray(blocks), jnp.asarray(cols)
