"""Vertex-partitioned distributed label propagation (DESIGN.md §4).

Rows (vertices) are partitioned across a 1-D device view of the mesh via
``shard_map``; each device owns a contiguous ELL row block whose neighbor
ids index the GLOBAL label vector.  Per iteration:

    all-gather F  →  local fused update  →  δ-threshold + local frontier
    →  psum(any frontier) convergence flag

F is N·4 bytes total, so the all-gather is cheap relative to the edge work
(50M vertices → 200 MB across the pod, ~4 ms at ICI bandwidth — the
roofline's collective term; a halo-exchange variant that ships only
boundary labels is the documented §Perf iteration for higher-diameter
partitionings).

The body reuses the exact update semantics of ``core.propagate`` (same
fixpoint, same iteration count), so single-device tests transfer.

Two transports exist, both built by ``make_sharded_propagate_fn`` and
both wrapping the same pluggable per-shard *update* body
(``backend="ref"`` inlines the XLA Jacobi update, ``backend="ell_pallas"``
calls the fused ELL Pallas kernel over the shard's row block,
``backend="bsr"`` scatter-builds the shard's BSR tiles from the staged
ELL rows and aggregates with the ``bsr_spmv`` MXU kernel against the
reconstructed global F):

  * ``transport="allgather"`` — every shard's full F block is gathered
    per iteration.  Shape-only partitioning (contiguous row blocks),
    topology-free, the safe default.
  * ``transport="halo"`` — only each shard's EXPORT PREFIX (length
    ``export_max``) is gathered; rows must be laid out so every
    cross-shard-referenced row leads its shard
    (``graph.partition.build_halo_plan``).  The gathered prefixes are
    scattered back into a full-length substitute vector whose entries
    match the all-gathered F at every *referenced* position, so the
    update body — and therefore the fixpoint, iteration count, and the
    labels bit for bit — is identical to the all-gather transport while
    the collective ships Σ|exports| instead of N values.

``StreamShardPlan`` packages the all-gather transport for
``core.stream.StreamEngine``: one plan per bucket-ladder rung (shape),
reused across every batch that lands in that rung, holding the row
shardings for staging and the jitted (optionally f0-donating) runner.
``StreamHaloPlan`` is its halo twin: same per-rung lifecycle, plus the
rung's compiled export budget — the engine re-derives the export *layout*
per Δ_t on the host (stale exports within the budget are harmless: they
carry committed labels) and falls back to all-gather for any batch whose
exports overflow the budget.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.propagate import (PropagateResult, PropagationProblem,
                                  bsr_update_island, gather_rows,
                                  update_island)
from repro.graph.structures import PAD
from repro.kernels.bsr_spmv import bsr_spmv, fill_bsr_blocks
from repro.kernels.ell_propagate import ell_propagate_step
from repro.kernels.platform import resolve_interpret


def shard_map(f, *, mesh, in_specs, out_specs):
    # the replication check is advisory, and the per-shard bodies (Pallas
    # calls, the solve's while loop) are not annotated for it
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

# "landmark" has no mesh body of its own: its hot solve IS the ref body
# (the hot/cold split happens at staging, in the engine), so it rides the
# ref branch of make_sharded_propagate_fn under both transports.
STREAM_BACKENDS = ("ref", "ell_pallas", "bsr", "landmark")
TRANSPORTS = ("allgather", "halo")


# ---------------------------------------------------------------------- #
# Serving read placement (device-resident LabelView under a mesh)
# ---------------------------------------------------------------------- #

def read_replica_device(mesh: jax.sharding.Mesh) -> jax.Device | None:
    """First visible device NOT in ``mesh`` — the serving read replica.

    A mesh deployment that leaves a device out of the solver mesh gets
    strictly better read behaviour than single-device serving: the
    committed ``DeviceLabelView`` is published to the replica, so query
    gathers never queue behind solve programs or snapshot staging on the
    solver devices' execution streams (programs on one device
    serialize).  Returns None when the mesh covers every device — then
    ``view_sharding`` is the fallback placement.
    """
    in_mesh = {d.id for d in mesh.devices.flat}
    for d in jax.devices():
        if d.id not in in_mesh:
            return d
    return None


def view_sharding(mesh: jax.sharding.Mesh) -> jax.sharding.NamedSharding:
    """Row-sharded placement for the committed view's node axis, over all
    mesh axes — for deployments whose ``f`` is too big for one device.
    The jitted query gather then compiles to a sharded lookup (GSPMD
    inserts the collectives); prefer ``read_replica_device`` when a
    spare device exists — a replica gather needs no collective at all.
    """
    return jax.sharding.NamedSharding(mesh, P(mesh.axis_names))


def read_placement(mesh: jax.sharding.Mesh | None):
    """Default placement for published device views: the committed-view
    device (None → jax's default) without a mesh; with one, the read
    replica if a spare device exists, else row-sharded over the mesh."""
    if mesh is None:
        return None
    return read_replica_device(mesh) or view_sharding(mesh)


class ShardedProblem(NamedTuple):
    """PropagationProblem padded to a multiple of the device count."""

    problem: PropagationProblem
    n_orig: int


def pad_problem(problem: PropagationProblem, n_devices: int) -> ShardedProblem:
    n = problem.num_unlabeled
    pad = (-n) % n_devices
    if pad == 0:
        return ShardedProblem(problem, n)
    padded = PropagationProblem(
        nbr=jnp.pad(problem.nbr, ((0, pad), (0, 0)), constant_values=PAD),
        wgt=jnp.pad(problem.wgt, ((0, pad), (0, 0))),
        wl0=jnp.pad(problem.wl0, (0, pad)),
        wl1=jnp.pad(problem.wl1, (0, pad)),
        valid=jnp.pad(problem.valid, (0, pad)),
    )
    return ShardedProblem(padded, n)


def make_sharded_propagate_fn(
    mesh,
    *,
    backend: str = "ref",
    delta: float = 1e-4,
    max_iters: int = 100_000,
    block_rows: int = 512,
    interpret: bool | None = None,
    donate: bool = False,
    transport: str = "allgather",
    export_max: int | None = None,
    block_size: int = 0,
    num_slots: int = 0,
):
    """Build the jitted sharded propagation step (lowerable with
    ShapeDtypeStructs for the LP roofline dry-run).

    The per-shard update body is the selected single-device backend:
    ``"ref"`` inlines the exact ``core.propagate`` Jacobi arithmetic (same
    per-row reduction order, so sharded labels are bit-identical to the
    single-device engine); ``"ell_pallas"`` runs the fused ELL kernel over
    the shard's row block against the gathered global F
    (``row_offset`` keys the kernel's F reads to this shard's rows);
    ``"bsr"`` scatter-builds the shard's BSR tiles from its staged ELL
    rows (``kernels.bsr_spmv.fill_bsr_blocks`` — inside the jit, so the
    tiles never exist on the host) and aggregates with the ``bsr_spmv``
    MXU kernel against the reconstructed global F.  The bsr runner takes
    one extra row-sharded input, the per-edge ``slot`` map, and its
    ``run`` signature is ``(nbr, wgt, wl0, wl1, valid, slot, f, fr)``;
    ``block_size``/``num_slots`` fix the compiled tile layout (callers
    keep snapshots whose slot requirement exceeds ``num_slots`` off this
    runner — the streaming engine falls back to ell_pallas for such a
    Δ_t).  Because the tile layout is part of the program, bsr labels
    are bit-identical across the two transports for the same row layout
    (the engine stages bsr snapshots in the halo layout under BOTH
    transports for exactly this reason).

    ``transport`` picks the per-iteration collective: ``"allgather"``
    ships every shard's full F block; ``"halo"`` ships only the leading
    ``export_max`` rows of each shard and scatters them into a
    full-length substitute vector (own block overwritten with exact local
    values).  With rows laid out so every cross-shard-referenced row sits
    inside its shard's export prefix (``graph.partition.build_halo_plan``),
    the substitute agrees with the all-gathered F at every position the
    update body reads, so both transports produce bit-identical labels —
    the halo form just moves Σ|exports|·4 instead of N·4 bytes per
    gather.  Positions outside any export prefix are zero-filled; they
    are only ever touched by PAD-masked lanes whose contribution is
    zeroed (ref) or weight-masked (ell_pallas).

    ``donate=True`` donates the f0 argument *per shard* — each device
    recycles its own label-block allocation across Δ_t (no-op on CPU).
    """
    if backend not in STREAM_BACKENDS:
        raise ValueError(
            f"sharded backend {backend!r} not supported; want one of "
            f"{STREAM_BACKENDS}")
    if transport not in TRANSPORTS:
        raise ValueError(
            f"transport {transport!r} not supported; want one of {TRANSPORTS}")
    if transport == "halo" and (export_max is None or export_max < 1):
        raise ValueError("transport='halo' needs export_max >= 1")
    if backend == "bsr" and (block_size < 1 or num_slots < 1):
        raise ValueError("sharded backend='bsr' needs block_size >= 1 and "
                         "num_slots >= 1 (the compiled tile layout)")
    axes = mesh.axis_names
    n_dev = int(mesh.devices.size)
    delta_ = jnp.float32(delta)
    row = P(axes)  # rows sharded over ALL mesh axes (flattened view)
    row2 = P(axes, None)
    interpret = resolve_interpret(interpret)

    # bsr takes one extra row-sharded input (the per-edge tile-slot map)
    in_specs = ((row2, row2, row, row, row, row2, row, row)
                if backend == "bsr" else
                (row2, row2, row, row, row, row, row))

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=(row, P(), P(), P()),
    )
    def run(nbr, wgt, wl0, wl1, valid, *rest):
        slot = rest[0] if backend == "bsr" else None
        f_loc, fr_loc = rest[-2:]
        mask = nbr != PAD
        idx = jnp.where(mask, nbr, 0)
        m = f_loc.shape[0]

        if transport == "halo":
            e = min(export_max, m)
            my = jax.lax.axis_index(axes)
            my_row0 = my * m
            owner = idx // m  # (m, K) owning shard of each referenced row
            offset = idx % m
            # (m, K) positions into the [local block | export prefixes]
            # concat buffer built per gather below: local references read
            # their own block, cross-shard ones read inside the owner's
            # export prefix (guaranteed by the halo row layout; masked
            # PAD lanes resolve to idx 0 = shard 0's prefix row 0, a
            # defined value the update masks out).  Integer select, so
            # the floating-point values reach the update through a plain
            # gather — the same producer-op shape as the all-gather
            # transport, which keeps XLA emitting the update arithmetic
            # identically (bit-equality contract).
            pos = jnp.where(owner == my, offset,
                            m + owner * e + jnp.minimum(offset, e - 1))

            def gather_full(x_loc):
                """Full-length substitute vector (ell_pallas path: the
                fused kernel indexes F globally, so the export prefixes
                are scattered back into an (N,) buffer; own block is
                exact, so reads of local rows never go stale)."""
                ex = jax.lax.all_gather(x_loc[:e], axes, tiled=True)
                full = jnp.zeros((n_dev, m), x_loc.dtype)
                full = full.at[:, :e].set(ex.reshape(n_dev, e)).reshape(-1)
                return jax.lax.dynamic_update_slice(full, x_loc, (my_row0,))

            def gather_vals(x_loc):
                """(m, K) values of x at the referenced positions — the
                ref-body path: the collective ships only the (D, e)
                export prefixes and values are picked per reference from
                a small (m + D·e) concat buffer, never a full-length
                temporary."""
                ex = jax.lax.all_gather(x_loc[:e], axes, tiled=True)
                return gather_rows(jnp.concatenate([x_loc, ex]), pos)
        else:
            def gather_full(x_loc):
                return jax.lax.all_gather(x_loc, axes, tiled=True)

            def gather_vals(x_loc):
                return gather_rows(gather_full(x_loc), idx)

        if backend == "ell_pallas":
            # Pad the shard's row block to a multiple of the kernel tile
            # (the sharded twin of ops._pad_rows).  Pad rows never enter
            # the frontier, so their outputs are discarded by the slice.
            r = min(block_rows, m)
            m_pad = -r * (-m // r)
            rpad = ((0, m_pad - m), (0, 0))
            nbr_k = jnp.pad(nbr, rpad, constant_values=PAD)
            wgt_k = jnp.pad(wgt, rpad)
            wl0_k = jnp.pad(wl0, (0, m_pad - m))
            wl1_k = jnp.pad(wl1, (0, m_pad - m))
        elif backend == "bsr":
            # Scatter the shard's staged ELL rows into its BSR tiles once
            # per solve (loop-invariant; block columns stay GLOBAL so the
            # SpMV consumes the reconstructed full-length F directly).
            # Tiles whose columns fall outside any export prefix carry
            # exact-zero weights, so the halo transport's zero-filled
            # substitute positions contribute identical bits to the
            # all-gathered values — the cross-transport equality argument.
            blocks, bcols = fill_bsr_blocks(
                nbr, wgt, slot, block_size=block_size, num_slots=num_slots)
            wall = jnp.sum(wgt, axis=1) + wl0 + wl1

        def update(f_l, fr_l):
            if backend == "bsr":
                f_full = gather_full(f_l)  # (N,) — the collective
                y = bsr_spmv(blocks, bcols, f_full, interpret=interpret)[:m]
                f_all = bsr_update_island(y, wl1, wall, f_l)
                f_new = jnp.where(fr_l & valid, f_all, f_l)
                changed = (jnp.abs(f_new - f_l) > delta_) & valid
                return f_new, changed
            if backend == "ell_pallas":
                f_full = gather_full(f_l)  # (N,) — the collective
                row0 = jax.lax.axis_index(axes) * m
                f_new, changed = ell_propagate_step(
                    nbr_k, wgt_k, wl0_k, wl1_k,
                    jnp.pad(fr_l, (0, m_pad - m)), f_full, delta=delta,
                    block_rows=r, interpret=interpret, row_offset=row0)
                return f_new[:m], changed[:m] & valid
            f_u = f_l
            # the barrier-isolated Jacobi island — the exact HLO shared
            # with the single-device engine, so every transport contracts
            # the arithmetic identically (bit-equality contract); the
            # transports differ only in how the (m, K) neighbor values
            # are fetched, never in their bits
            f_new = update_island(wgt, wl0, wl1, f_u, gather_vals(f_l), mask)
            f_new = jnp.where(fr_l, f_new, f_u)
            changed = (jnp.abs(f_new - f_u) > delta_) & valid
            return f_new, changed

        def body(state):
            f_l, fr_l, it, _ = state
            f_new, changed_l = update(f_l, fr_l)
            nbr_changed = jnp.any(gather_vals(changed_l) & mask, axis=1)
            fr_new = (changed_l | nbr_changed) & valid
            resid = jax.lax.pmax(
                jnp.max(jnp.abs(f_new - f_l), initial=0.0), axes)
            return f_new, fr_new, it + 1, resid

        def cond(state):
            _, fr_l, it, _ = state
            any_frontier = jax.lax.pmax(fr_l.any().astype(jnp.int32), axes)
            return jnp.logical_and(any_frontier > 0, it < max_iters)

        f_l, fr_l, iters, resid = jax.lax.while_loop(
            cond, body, (f_loc, fr_loc, jnp.int32(0), jnp.float32(0)))
        done = jax.lax.pmax(fr_l.any().astype(jnp.int32), axes) == 0
        return f_l, iters, done, resid

    f0_idx = 6 if backend == "bsr" else 5  # slot shifts the arg list
    return jax.jit(run, donate_argnums=(f0_idx,) if donate else ())


def make_propagate_fn(mesh, delta: float = 1e-4, max_iters: int = 100_000):
    """All-gather ``ref`` transport with the historical one-shot signature."""
    return make_sharded_propagate_fn(mesh, backend="ref", delta=delta,
                                     max_iters=max_iters)


def distributed_propagate(
    problem: PropagationProblem,
    f0: jax.Array,
    frontier0: jax.Array,
    mesh: jax.sharding.Mesh,
    delta: float = 1e-4,
    max_iters: int = 100_000,
) -> PropagateResult:
    """Run DynLP Step 3 with vertices sharded over every mesh device."""
    n_dev = mesh.devices.size
    sp = pad_problem(problem, n_dev)
    p = sp.problem
    n = p.num_unlabeled
    f0 = jnp.pad(f0.astype(jnp.float32), (0, n - len(f0)))
    frontier0 = jnp.pad(frontier0, (0, n - len(frontier0))) & p.valid
    run = make_propagate_fn(mesh, delta=delta, max_iters=max_iters)
    f, iters, converged, resid = run(
        p.nbr, p.wgt, p.wl0, p.wl1, p.valid, f0, frontier0)
    return PropagateResult(
        f=f[: sp.n_orig], iterations=iters, converged=converged,
        max_residual=resid)


# --------------------------------------------------------------------- #
# Streaming partition plans (core.stream.StreamEngine mesh mode)
# --------------------------------------------------------------------- #
# One jitted runner per (mesh, backend, hyperparams) — rungs of the same
# stream share it (each rung is one more shape specialization in its jit
# cache, which is exactly what ``sharded_cache_size`` counts).  Both
# caches are process-lifetime, like the module-level jits in kernels.ops.
_FN_CACHE: dict = {}
_PLAN_CACHE: dict = {}


@dataclasses.dataclass(frozen=True)
class StreamShardPlan:
    """Shape-keyed partition plan: one per bucket-ladder rung.

    Holds everything a stream needs to run batches of one bucket shape on
    a mesh — the row shardings used to stage host snapshots/vectors and
    the jitted all-gather runner.  Plans are topology-independent
    (contiguous row blocks), so a single plan serves every batch whose
    padded snapshot lands in its rung; only a ladder regrow builds a new
    one (``StreamEngine.plan_builds`` ≤ rungs touched, asserted in
    tests/test_stream_sharded.py).
    """

    mesh: jax.sharding.Mesh
    bucket_key: tuple[int, int]
    backend: str
    delta: float
    max_iters: int
    block_rows: int
    interpret: bool | None
    row_sharding: jax.sharding.NamedSharding
    row2_sharding: jax.sharding.NamedSharding
    run: object  # jitted shard_map propagation fn
    # bsr plans carry their compiled tile layout (0 for other backends):
    # the streaming engine memoizes one plan per rung and checks each
    # Δ_t's slot requirement against num_slots before running on it.
    block_size: int = 0
    num_slots: int = 0

    transport = "allgather"

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def put_row(self, x) -> jax.Array:
        """Stage a per-row host vector with this plan's row sharding."""
        return jax.device_put(x, self.row_sharding)

    def put_row2(self, x) -> jax.Array:
        """Stage a (rows, K) host array row-sharded, K replicated."""
        return jax.device_put(x, self.row2_sharding)

    def put_problem(self, nbr, wgt, wl0, wl1, valid) -> PropagationProblem:
        return PropagationProblem(
            nbr=self.put_row2(nbr), wgt=self.put_row2(wgt),
            wl0=self.put_row(wl0), wl1=self.put_row(wl1),
            valid=self.put_row(valid))

    def __call__(self, problem: PropagationProblem, f0: jax.Array,
                 frontier0: jax.Array, slot=None) -> PropagateResult:
        if tuple(problem.nbr.shape) != self.bucket_key:
            raise ValueError(
                f"problem shape {problem.nbr.shape} does not match plan "
                f"rung {self.bucket_key}")
        if f0.dtype != jnp.float32:
            f0 = f0.astype(jnp.float32)
        if self.backend == "bsr":
            if slot is None:
                raise ValueError("bsr shard plan needs the per-edge slot "
                                 "map (stage it with put_row2)")
            args = (problem.nbr, problem.wgt, problem.wl0, problem.wl1,
                    problem.valid, slot, f0, frontier0)
        else:
            args = (problem.nbr, problem.wgt, problem.wl0, problem.wl1,
                    problem.valid, f0, frontier0)
        f, iters, done, resid = self.run(*args)
        return PropagateResult(f=f, iterations=iters, converged=done,
                               max_residual=resid)


@dataclasses.dataclass(frozen=True)
class StreamHaloPlan(StreamShardPlan):
    """Per-rung halo-exchange plan: ``StreamShardPlan`` + the rung's
    compiled export-prefix budget.

    The export *budget* (``export_max``) is fixed once per rung so the
    jitted runner compiles once; the export *layout* (which rows lead
    each shard) is re-derived per Δ_t on the host by the engine and is
    allowed to overshoot the real export set — stale/extra prefix rows
    ship committed labels, which is harmless.  A batch whose export
    counts exceed the budget can't run on this plan; the engine falls
    back to its all-gather twin for that Δ_t.
    """

    export_max: int = 0

    transport = "halo"


def _sharded_run_for(mesh, *, backend, delta, max_iters, block_rows,
                     interpret, donate, transport="allgather",
                     export_max=None, block_size=0, num_slots=0):
    """Fetch (or build, memoized) the jitted runner for one hyperparameter
    set.  All-gather runners are shared across every rung (each rung is
    one shape specialization in the jit cache); halo runners additionally
    key on the rung's export budget, bsr runners on the compiled tile
    layout."""
    fn_key = (mesh, backend, float(delta), max_iters, block_rows, interpret,
              donate, transport, export_max, block_size, num_slots)
    run = _FN_CACHE.get(fn_key)
    if run is None:
        run = make_sharded_propagate_fn(
            mesh, backend=backend, delta=delta, max_iters=max_iters,
            block_rows=block_rows, interpret=interpret, donate=donate,
            transport=transport, export_max=export_max,
            block_size=block_size, num_slots=num_slots)
        _FN_CACHE[fn_key] = run
    return fn_key, run


def _check_bucket(bucket_key, mesh, block_size=0):
    u_pad, _ = bucket_key
    n_dev = mesh.devices.size
    if u_pad % n_dev != 0:
        raise ValueError(
            f"bucket rows {u_pad} not divisible by mesh device count "
            f"{n_dev}; build snapshots with row_multiple={n_dev}")
    if block_size and (u_pad // n_dev) % block_size != 0:
        raise ValueError(
            f"bsr needs each shard's {u_pad // n_dev} rows to be a "
            f"multiple of block_size {block_size}; build snapshots with "
            f"row_multiple={n_dev * block_size}")


def build_stream_plan(
    mesh,
    bucket_key: tuple[int, int],
    *,
    backend: str = "ref",
    delta: float = 1e-4,
    max_iters: int = 100_000,
    block_rows: int = 512,
    interpret: bool | None = None,
    donate: bool = True,
    block_size: int = 0,
    num_slots: int = 0,
) -> StreamShardPlan:
    """Build (or fetch, memoized) the all-gather partition plan for one
    ladder rung.

    Rows must shard evenly: ``bucket_key[0]`` has to be a multiple of the
    mesh's device count (``core.snapshot.build_host_problem`` pads buckets
    with ``row_multiple=mesh.devices.size`` to guarantee it — times
    ``block_size`` for bsr plans, whose shards must also tile evenly).
    """
    _check_bucket(bucket_key, mesh, block_size if backend == "bsr" else 0)
    fn_key, run = _sharded_run_for(
        mesh, backend=backend, delta=delta, max_iters=max_iters,
        block_rows=block_rows, interpret=interpret, donate=donate,
        block_size=block_size, num_slots=num_slots)
    key = (fn_key, tuple(bucket_key))
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        axes = mesh.axis_names
        plan = StreamShardPlan(
            mesh=mesh, bucket_key=tuple(bucket_key), backend=backend,
            delta=float(delta), max_iters=max_iters, block_rows=block_rows,
            interpret=interpret,
            row_sharding=jax.sharding.NamedSharding(mesh, P(axes)),
            row2_sharding=jax.sharding.NamedSharding(mesh, P(axes, None)),
            run=run, block_size=block_size, num_slots=num_slots)
        _PLAN_CACHE[key] = plan
    return plan


def build_stream_halo_plan(
    mesh,
    bucket_key: tuple[int, int],
    export_max: int,
    *,
    backend: str = "ref",
    delta: float = 1e-4,
    max_iters: int = 100_000,
    block_rows: int = 512,
    interpret: bool | None = None,
    donate: bool = True,
    block_size: int = 0,
    num_slots: int = 0,
) -> StreamHaloPlan:
    """Halo twin of ``build_stream_plan``: one plan per (rung, export
    budget), memoized.  Callers stage problems in the export-prefix row
    layout of ``graph.partition.build_halo_plan`` and guarantee
    ``export_counts.max() <= export_max`` for every batch they run on it.
    """
    _check_bucket(bucket_key, mesh, block_size if backend == "bsr" else 0)
    m = bucket_key[0] // mesh.devices.size
    export_max = int(min(max(1, export_max), m))
    fn_key, run = _sharded_run_for(
        mesh, backend=backend, delta=delta, max_iters=max_iters,
        block_rows=block_rows, interpret=interpret, donate=donate,
        transport="halo", export_max=export_max,
        block_size=block_size, num_slots=num_slots)
    key = (fn_key, tuple(bucket_key))
    plan = _PLAN_CACHE.get(key)
    if plan is None:
        axes = mesh.axis_names
        plan = StreamHaloPlan(
            mesh=mesh, bucket_key=tuple(bucket_key), backend=backend,
            delta=float(delta), max_iters=max_iters, block_rows=block_rows,
            interpret=interpret,
            row_sharding=jax.sharding.NamedSharding(mesh, P(axes)),
            row2_sharding=jax.sharding.NamedSharding(mesh, P(axes, None)),
            run=run, block_size=block_size, num_slots=num_slots,
            export_max=export_max)
        _PLAN_CACHE[key] = plan
    return plan


def sharded_cache_size() -> int:
    """Summed jit-cache entries of every streaming shard_map runner —
    folded into ``kernels.ops.compile_cache_size`` so the stream's
    recompile accounting covers the mesh path too."""
    return sum(fn._cache_size() for fn in _FN_CACHE.values())


# --------------------------------------------------------------------- #
# Sharded embedding-store sweep plans (ingest.ShardedEmbeddingStore)
# --------------------------------------------------------------------- #
# Same lifecycle as the stream plans above: one jitted shard_map runner
# per (mesh, argkmin hyperparams) in _STORE_FN_CACHE — every capacity
# rung / batch bucket is one more shape specialization in its jit cache,
# which is what ``store_sweep_cache_size`` counts and
# ``ingest.ingest_ladder_bound(sharded=True)`` bounds — plus one
# lightweight StoreShardPlan per (runner, rung) holding the staging
# shardings.
_STORE_FN_CACHE: dict = {}
_STORE_PLAN_CACHE: dict = {}


@dataclasses.dataclass(frozen=True)
class StoreShardPlan:
    """Per-rung plan for the move-the-batch argkmin sweep over a
    row-sharded embedding store.

    Each device keeps its ``cap / D`` store rows resident and receives
    the replicated batch; the runner executes
    ``kernels.argkmin.shard_sweep_body`` under shard_map — per-shard
    top-(k+margin) with global row ids, one packed all-gather of the
    per-shard lists, device-side ``merge_topk`` reduction — and returns
    ``(val, idx)`` and the displacement mask replicated (the mask's
    shards gather back into exactly the single-device mask, so the host
    pull is one local copy).  The merged lists are bit-identical to the
    single-device ``argkmin_candidates`` (see the argkmin module
    docstring for the tie argument), so canonical host re-selection
    keeps every graph byte-identical to the unsharded path.
    """

    mesh: jax.sharding.Mesh
    cap_key: tuple[int, int]  # (capacity rung, padded emb dim)
    backend: str              # resolved: "pallas" | "xla"
    block_rows: int
    interpret: bool | None
    row_sharding: jax.sharding.NamedSharding
    row2_sharding: jax.sharding.NamedSharding
    rep_sharding: jax.sharding.NamedSharding
    run: object  # jitted shard_map sweep fn (static topk)

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    def sweep(self, emb, valid, kth, batch, bvalid, base_id, slack, *,
              topk: int):
        """Run the sharded candidate sweep for one appended batch."""
        if tuple(emb.shape) != self.cap_key:
            raise ValueError(
                f"store shape {tuple(emb.shape)} does not match plan rung "
                f"{self.cap_key}")
        return self.run(emb, valid, kth, batch, bvalid,
                        jnp.int32(base_id), jnp.float32(slack), topk=topk)


def _store_sweep_for(mesh, *, backend, block_rows, interpret):
    """Fetch (or build, memoized) the jitted sharded-sweep runner for one
    (mesh, argkmin hyperparams) set; rungs/batches share it."""
    key = (mesh, backend, block_rows, interpret)
    run = _STORE_FN_CACHE.get(key)
    if run is None:
        # lazy: argkmin pulls graph.knn, which ingest-only processes may
        # never need until a sharded store exists
        from repro.kernels.argkmin import shard_sweep_body
        axes = mesh.axis_names

        def sweep(emb, valid, kth, batch, bvalid, base_id, slack, *, topk):
            body = shard_map(
                functools.partial(
                    shard_sweep_body, axes=axes, topk=topk, backend=backend,
                    block_rows=block_rows, interpret=interpret),
                mesh=mesh,
                in_specs=(P(axes, None), P(axes), P(axes),
                          P(), P(), P(), P()),
                out_specs=(P(), P(), P()))
            return body(emb, valid, kth, batch, bvalid, base_id, slack)

        run = jax.jit(sweep, static_argnames=("topk",))
        _STORE_FN_CACHE[key] = run
    return key, run


def build_store_shard_plan(
    mesh,
    cap_key: tuple[int, int],
    *,
    backend: str = "auto",
    block_rows: int = 256,
    interpret: bool | None = None,
) -> StoreShardPlan:
    """Build (or fetch, memoized) the sharded-store sweep plan for one
    capacity rung.

    ``cap_key`` is ``(capacity, dim_pad)``; capacity must divide evenly
    over the mesh (the store ladder floor guarantees it for power-of-two
    meshes).  ``backend="auto"`` resolves to Pallas on TPU, XLA elsewhere
    — resolution happens here so auto and explicit callers share runners.
    """
    cap, dp = cap_key
    n_dev = int(mesh.devices.size)
    if cap % n_dev:
        raise ValueError(
            f"store capacity {cap} not divisible by mesh device count "
            f"{n_dev}")
    from repro.kernels.argkmin import resolve_backend
    backend = resolve_backend(backend)
    if backend == "pallas":
        interpret = resolve_interpret(interpret)
    fn_key, run = _store_sweep_for(
        mesh, backend=backend, block_rows=block_rows, interpret=interpret)
    key = (fn_key, (int(cap), int(dp)))
    plan = _STORE_PLAN_CACHE.get(key)
    if plan is None:
        axes = mesh.axis_names
        plan = StoreShardPlan(
            mesh=mesh, cap_key=(int(cap), int(dp)), backend=backend,
            block_rows=block_rows, interpret=interpret,
            row_sharding=jax.sharding.NamedSharding(mesh, P(axes)),
            row2_sharding=jax.sharding.NamedSharding(mesh, P(axes, None)),
            rep_sharding=jax.sharding.NamedSharding(mesh, P()),
            run=run)
        _STORE_PLAN_CACHE[key] = plan
    return plan


def store_sweep_cache_size() -> int:
    """Summed jit-cache entries of every sharded store-sweep runner —
    folded into ``ingest.ingest_cache_size`` so the ingest recompile gate
    covers the mesh path too."""
    return sum(fn._cache_size() for fn in _STORE_FN_CACHE.values())


def make_propagate_halo_fn(mesh, rows_per_shard: int, export_max: int,
                           delta: float = 1e-4, max_iters: int = 100_000):
    """Historical one-shot halo entry point — now a thin wrapper over the
    unified ``make_sharded_propagate_fn(transport="halo")`` builder, so
    the one-shot API and the streaming ``StreamHaloPlan`` path exercise
    the same code.  ``rows_per_shard`` is kept for signature compat (the
    traced shapes imply it)."""
    del rows_per_shard
    return make_sharded_propagate_fn(
        mesh, backend="ref", delta=delta, max_iters=max_iters,
        transport="halo", export_max=export_max)


def distributed_propagate_halo(
    problem: PropagationProblem,  # rows already in HaloPlan layout
    f0: jax.Array,
    frontier0: jax.Array,
    mesh: jax.sharding.Mesh,
    export_max: int,
    delta: float = 1e-4,
    max_iters: int = 100_000,
) -> PropagateResult:
    n_dev = mesh.devices.size
    n = problem.num_unlabeled
    assert n % n_dev == 0, "caller pads via build_halo_plan"
    run = make_propagate_halo_fn(mesh, n // n_dev, export_max,
                                 delta=delta, max_iters=max_iters)
    p = problem
    f, iters, converged, resid = run(
        p.nbr, p.wgt, p.wl0, p.wl1, p.valid, f0.astype(jnp.float32), frontier0)
    return PropagateResult(f=f, iterations=iters, converged=converged,
                           max_residual=resid)
