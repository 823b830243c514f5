"""Pallas TPU kernel: tiled cosine argkmin over the device embedding store.

One pass over the store answers both questions an arriving batch poses
(the DynLP "necessary updates only" discipline applied to construction):

  1. **New-row candidates** — for every batch row, the top-(k + margin)
     store rows by fast similarity.  These are *candidate supersets*: the
     final top-k is re-selected canonically on the host (``graph.knn``
     module docstring), so the kernel's matmul rounding can never leak
     into edge weights.
  2. **Displaced-row pruning** — the mask of existing store rows whose
     current k-th weight at least one batch point beats (within
     ``selection_slack``).  Only these rows pay a list merge on the host;
     everything else is untouched.

Layout: the store is row-indexed by *global vertex id* (it never
compacts; dead rows are masked out of ``valid``), and the batch is
appended to the store **before** the call, so batch rows are ordinary
columns for each other — within-batch neighbors fall out for free and
self-matches are excluded by the ``store_row == base_id + query_row``
diagonal.

Grid: (C // R,) over store row tiles.  The batch block and the running
best-candidate accumulator — an (M, 128) lane block whose first TK lanes
hold the list — use constant index maps (VMEM resident across grid
steps, ``@pl.when`` init at step 0 — the standard cross-step
accumulation pattern); the displacement mask is written per tile.
Per-row masks and thresholds travel as (1, C) int32/f32 rows, so Mosaic
sees only 2-D, lane-dense operands.  Ties select the lowest store row,
matching both ``lax.top_k`` and the host oracle's canonical order, so
mass-duplicate inputs keep identical candidate coverage on every path.
Both paths contract at ``Precision.HIGHEST`` (full f32), which is what
``selection_slack`` is sized for.

The ``xla`` twin (one fused jit: matmul + ``lax.top_k`` + mask) serves
non-TPU hardware; ``backend="auto"`` picks Pallas on TPU, XLA elsewhere.
Off-TPU the Pallas pass runs interpreted — tests and
``benchmarks/ingest_lp.py --check`` use that to verify agreement.

**Sharded sweep (move-the-batch orientation).**  When the store is
row-sharded over a mesh (``ingest.ShardedEmbeddingStore``), each device
runs the same pass against only its resident rows with ``row0`` set to
its shard's global row offset — candidate ids and the ``base_id``
comparisons are global, so per-shard outputs compose without any host
renumbering — then ``shard_sweep_body`` all-gathers the per-shard
top-(k+margin) lists and ``merge_topk`` reduces them to the global
top-(k+margin).  Shard row blocks are contiguous-ascending and each
per-shard list orders tied values by ascending id, so the merge's
ties→lowest-position rule IS ties→lowest-global-id: the merged list is
bit-identical to the single-device pass, and the displacement masks
concatenate to the single-device mask because each row's dot product is
the same reduction wherever it lives.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.graph.knn import SELECT_MARGIN
from repro.kernels.platform import on_tpu, resolve_interpret

_INT_MAX = 2**31 - 1  # python literal: a jnp scalar here would be a captured tracer in the kernel
_LANES = 128  # the running top-k lists live in one lane-aligned (M, 128) block

# Both paths contract in full float32: ``selection_slack`` is sized for f32
# accumulation, and a TPU dot left at its default precision would round
# through bfloat16 and drift by ~1e-3 — far past the slack.
_PRECISION = jax.lax.Precision.HIGHEST


def _kernel(sc_ref, store_ref, valid_ref, thr_ref, batch_ref, bvalid_ref,
            val_ref, idx_ref, disp_ref, *, topk):
    # Every operand is 2-D with a lane-dense minor axis: per-row masks and
    # thresholds ride as (1, R) rows and batch flags as an (M, 1) column,
    # int32 instead of bool, so Mosaic never has to reshape a 1-D vector.
    i = pl.program_id(0)
    tile = store_ref[...]  # (R, D)
    batch = batch_ref[...]  # (M, D) — VMEM resident across tiles
    r = tile.shape[0]
    m = batch.shape[0]
    # sc = (row0, base_id): row0 is this store block's global row offset (0
    # single-device; the shard's offset under the sharded sweep) — all row
    # ids downstream of rows_g are global, so per-shard outputs merge
    # without renumbering
    base_id = sc_ref[1]
    rows_g = sc_ref[0] + i * r + jax.lax.broadcasted_iota(jnp.int32, (1, r), 1)
    q_ids = base_id + jax.lax.broadcasted_iota(jnp.int32, (m, 1), 0)

    s = jax.lax.dot_general(batch, tile, (((1,), (1,)), ((), ())),
                            precision=_PRECISION,
                            preferred_element_type=jnp.float32)  # (M, R)
    w = (s + 1.0) * 0.5
    valid = valid_ref[...] != 0  # (1, R)
    wm = jnp.where(valid & (rows_g != q_ids), w, -jnp.inf)

    # displacement pruning: old valid rows some batch point beats
    # (thr = kth - slack, precomputed per row)
    wq = jnp.where(bvalid_ref[...] != 0, w, -jnp.inf)
    colmax = jnp.max(wq, axis=0, keepdims=True)  # (1, R)
    old = valid & (rows_g < base_id)
    disp_ref[...] = (old & (colmax > thr_ref[...])).astype(jnp.int32)

    # fold this tile into the running top-k (ties -> lowest store row); the
    # accumulator's lanes past topk hold -inf and never win
    @pl.when(i == 0)
    def _init():
        val_ref[...] = jnp.full(val_ref.shape, -jnp.inf, jnp.float32)
        idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)

    tile_i = jnp.broadcast_to(rows_g, (m, r))
    lane = jax.lax.broadcasted_iota(jnp.int32, val_ref.shape, 1)

    def pick(t, carry):
        """Move the t-th best (value, lowest id) of accumulator + tile to
        output lane t, and retire it from whichever side held it."""
        acc_v, acc_i, wm, out_v, out_i = carry
        mx = jnp.maximum(jnp.max(acc_v, axis=1, keepdims=True),
                         jnp.max(wm, axis=1, keepdims=True))  # (M, 1)
        acc_tie = acc_v == mx
        tile_tie = wm == mx
        sel = jnp.minimum(
            jnp.min(jnp.where(acc_tie, acc_i, _INT_MAX), axis=1, keepdims=True),
            jnp.min(jnp.where(tile_tie, tile_i, _INT_MAX), axis=1,
                    keepdims=True))
        out_v = jnp.where(lane == t, mx, out_v)
        out_i = jnp.where(lane == t, sel, out_i)
        acc_v = jnp.where(acc_tie & (acc_i == sel), -jnp.inf, acc_v)
        wm = jnp.where(tile_tie & (tile_i == sel), -jnp.inf, wm)
        return acc_v, acc_i, wm, out_v, out_i

    _, _, _, out_v, out_i = jax.lax.fori_loop(
        0, topk, pick,
        (val_ref[...], idx_ref[...], wm,
         jnp.full(val_ref.shape, -jnp.inf, jnp.float32),
         jnp.zeros(idx_ref.shape, jnp.int32)))
    val_ref[...] = out_v
    idx_ref[...] = out_i


def _argkmin_pallas_impl(store, valid, kth, batch, batch_valid, base_id,
                         slack, row0, topk, block_rows, interpret):
    """Unjitted Pallas pass over one (shard-local or whole) store block;
    ``row0`` is the block's global row offset."""
    c, d = store.shape
    m = batch.shape[0]
    r = min(block_rows, c)
    assert c % r == 0, (c, r)
    assert topk <= _LANES, topk
    const = lambda *shape: pl.BlockSpec(shape, lambda i, sc: (0,) * len(shape))
    row_block = pl.BlockSpec((1, r), lambda i, sc: (0, i))
    val, idx, disp = pl.pallas_call(
        functools.partial(_kernel, topk=topk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,  # (row0, base_id)
            grid=(c // r,),
            in_specs=[
                pl.BlockSpec((r, d), lambda i, sc: (i, 0)),  # store tile
                row_block,        # valid (1, C) int32
                row_block,        # thr = kth - slack (1, C)
                const(m, d),      # batch
                const(m, 1),      # batch_valid (M, 1) int32
            ],
            out_specs=[const(m, _LANES), const(m, _LANES), row_block],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((m, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((m, _LANES), jnp.int32),
            jax.ShapeDtypeStruct((1, c), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.stack([jnp.asarray(row0, jnp.int32), jnp.asarray(base_id, jnp.int32)]),
      store, valid.astype(jnp.int32).reshape(1, c),
      (kth.astype(jnp.float32) - jnp.asarray(slack, jnp.float32)).reshape(1, c),
      batch, batch_valid.astype(jnp.int32).reshape(m, 1))
    return val[:, :topk], idx[:, :topk], disp[0] != 0


_argkmin_pallas = jax.jit(
    _argkmin_pallas_impl,
    static_argnames=("topk", "block_rows", "interpret"))


def _argkmin_xla_impl(store, valid, kth, batch, batch_valid, base_id, slack,
                      row0, topk):
    """Unjitted XLA pass over one (shard-local or whole) store block;
    ``row0`` is the block's global row offset — the shared arithmetic of
    the single-device jit and the per-shard body, so displacement bits
    and candidate values agree across both by construction."""
    c = store.shape[0]
    m = batch.shape[0]
    rows_g = row0 + jnp.arange(c, dtype=jnp.int32)
    # store-major orientation: on CPU XLA, (C, D) @ (D, M) with the big
    # operand on the left runs ~4x faster than batch @ store.T, and the
    # barrier stops XLA from folding the later transpose back into the
    # dot (which would silently restore the slow orientation)
    s = jax.lax.optimization_barrier(
        jnp.dot(store, batch.T, precision=_PRECISION,
                preferred_element_type=jnp.float32))  # (C, M)
    w = (s + 1.0) * 0.5
    old = valid & (rows_g < base_id)
    colmax = jnp.max(jnp.where(batch_valid[None, :], w, -jnp.inf), axis=1)
    disp = old & (colmax > kth - slack)
    self_mask = rows_g[None, :] == base_id + jnp.arange(m, dtype=jnp.int32)[:, None]
    wm = jnp.where(valid[None, :] & ~self_mask, w.T, -jnp.inf)
    val, idx = jax.lax.top_k(wm, topk)  # ties keep the lower index
    return val, (row0 + idx).astype(jnp.int32), disp


_argkmin_xla = jax.jit(_argkmin_xla_impl, static_argnames=("topk",))


def merge_topk(val_g, idx_g, topk: int):
    """Top-``topk`` merge of concatenated per-shard candidate lists.

    ``val_g``/``idx_g`` are ``(M, D·tk_loc)`` — shard s's list occupies
    columns ``[s·tk_loc, (s+1)·tk_loc)``.  ``lax.top_k`` breaks ties by
    lowest *position*; shard row blocks are contiguous-ascending and each
    shard list orders tied values by ascending global id, so lowest
    position ⇔ lowest global id — the canonical tie order of the
    single-device pass and the host oracle.
    """
    mval, pos = jax.lax.top_k(val_g, topk)
    midx = jnp.take_along_axis(idx_g, pos, axis=1)
    return mval, midx


def shard_sweep_body(emb_l, valid_l, kth_l, batch, bvalid, base_id, slack,
                     *, axes, topk, backend, block_rows, interpret):
    """Per-device body of the sharded store sweep (runs under shard_map).

    The shard's resident rows are the matmul operand; the replicated
    batch moved to it.  Runs the selected per-block pass with this
    shard's global ``row0``, then all-gathers the per-shard
    top-``tk_loc`` lists and merges to the global top-``topk``
    (``merge_topk``).  One collective moves everything: the f32 values
    are bitcast to int32 (exact) and packed beside the ids so the
    gather ships a single ``(M, 2·tk_loc)`` block per shard, and the
    displacement mask rides back replicated (a ``(C,)`` bool gather) so
    the host pull is one local copy instead of D shard reads.
    """
    c_loc = emb_l.shape[0]
    row0 = (jax.lax.axis_index(axes) * c_loc).astype(jnp.int32)
    tk_loc = min(topk, c_loc)  # D·tk_loc ≥ topk either way: coverage holds
    if backend == "pallas":
        val, idx, disp = _argkmin_pallas_impl(
            emb_l, valid_l, kth_l, batch, bvalid, base_id, slack, row0,
            tk_loc, block_rows, interpret)
    else:
        val, idx, disp = _argkmin_xla_impl(
            emb_l, valid_l, kth_l, batch, bvalid, base_id, slack, row0,
            tk_loc)
    packed = jnp.concatenate(
        [jax.lax.bitcast_convert_type(val, jnp.int32), idx], axis=1)
    packed_g = jax.lax.all_gather(packed, axes, axis=1, tiled=True)
    n_sh = packed_g.shape[1] // (2 * tk_loc)
    packed_g = packed_g.reshape(packed.shape[0], n_sh, 2, tk_loc)
    val_g = jax.lax.bitcast_convert_type(
        packed_g[:, :, 0, :], jnp.float32).reshape(packed.shape[0], -1)
    idx_g = packed_g[:, :, 1, :].reshape(packed.shape[0], -1)
    mval, midx = merge_topk(val_g, idx_g, topk)
    disp_g = jax.lax.all_gather(disp, axes, axis=0, tiled=True)
    return mval, midx, disp_g


def argkmin_candidates(
    store: jax.Array,        # (C, D) f32 normalized embeddings, row == global id
    valid: jax.Array,        # (C,) bool — initialized & alive (incl. the batch)
    kth: jax.Array,          # (C,) f32 — current k-th weight, -inf under-full
    batch: jax.Array,        # (M, D) f32 normalized new rows (already in store)
    batch_valid: jax.Array,  # (M,) bool — first m rows real, rest padding
    base_id: int,            # global id of batch row 0
    slack: float,            # selection_slack(D): pruning tolerance
    *,
    k: int,
    backend: str = "auto",
    block_rows: int = 256,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Fast-path candidates + displacement mask for one embedding batch.

    Returns ``(val (M, k+SELECT_MARGIN) f32, idx (M, k+SELECT_MARGIN)
    int32, disp (C,) bool)``; ``val == -inf`` marks empty candidate slots
    (callers must drop them before canonical re-selection).
    """
    topk = min(k + SELECT_MARGIN, store.shape[0])
    backend = resolve_backend(backend)
    if backend == "pallas":
        return _argkmin_pallas(store, valid, kth, batch, batch_valid,
                               base_id, slack, 0, topk, block_rows,
                               resolve_interpret(interpret))
    if backend == "xla":
        return _argkmin_xla(store, valid, kth, batch, batch_valid,
                            jnp.int32(base_id), jnp.float32(slack),
                            jnp.int32(0), topk)
    raise ValueError(f"unknown argkmin backend {backend!r}")


def resolve_backend(backend: str) -> str:
    """``"auto"`` -> the compiled Pallas pass on TPU, the XLA twin elsewhere."""
    if backend == "auto":
        return "pallas" if on_tpu() else "xla"
    return backend


def argkmin_cache_size() -> int:
    """Live jit cache entries across both argkmin backends (compile-once
    telemetry for the ingest ladder gate)."""
    return int(_argkmin_pallas._cache_size() + _argkmin_xla._cache_size())
