"""Kernel dispatch layer — a capability-declaring backend registry.

Every propagation backend registers a ``BackendSpec`` describing what it
can do; ``run_propagation``, ``select_backend`` and
``compile_cache_size`` iterate the registry instead of hard-coding
backend names, so adding a backend is one ``register_backend`` call:

  * ``sharded`` / ``transports`` — whether the backend has a mesh form
    (``core.distributed`` wraps its per-shard update body) and which
    per-sweep collectives that form supports;
  * ``auto_eligible(info, hw)`` — when ``backend="auto"`` may pick it,
    from the problem shape and the measured properties in
    ``ProblemInfo`` (the streaming engine measures the post-reorder BSR
    block fill factor at rung entry and feeds it back in here);
  * ``run`` / ``cache_entry_points`` — the (donate-capable) single-device
    entry point and the jitted functions whose cache sizes make up the
    compile-once accounting.

Registered backends:

  * ``"ref"``        — the XLA reference engine (``core.propagate``), the
                       right answer on CPU and the allclose oracle
                       everywhere else.
  * ``"ell_pallas"`` — the fused ELL Pallas kernel loop
                       (``propagate_pallas``).  Explicit-only: its
                       in-kernel VMEM gather does not lower on Mosaic,
                       so it runs interpreted off-TPU and a TPU compile
                       refuses it; auto never picks it.
  * ``"bsr"``        — block-sparse MXU path: the neighbor aggregation
                       runs as ``bsr_spmv`` over component-reordered
                       block-dense tiles built DIRECTLY from the ELL
                       tensor (``kernels.bsr_spmv.ell_bsr_layout`` +
                       device-side ``fill_bsr_blocks`` — O(nnz), no
                       dense (U, U) intermediate).  Sharded under both
                       transports; auto-eligible on TPU when the
                       post-reorder block fill factor clears
                       ``bsr_auto_fill_min`` (a
                       per-hardware registry property, like the tile
                       edge ``bsr_block_size``).
  * ``"landmark"``   — the APPROXIMATE hot/cold split for beyond-HBM
                       graphs (``kernels.landmark_propagate``): exact
                       barriered Jacobi on the hot working set, a
                       low-rank landmark pass for the cold tail.  The
                       hot/cold machinery lives in the streaming engine
                       (working-set tracking, cold-label folding, commit
                       refresh); standalone ``run_propagation`` calls
                       degrade to the exact ``ref`` body.  Unlike every
                       other backend its contract is a recorded hot-set
                       agreement floor, NOT bit-equality — see
                       docs/backends.md.  Auto-eligible only when the
                       caller declares ``ProblemInfo.landmark_ready``
                       (the engine does, once landmark state is
                       configured and sampled) and the row count clears
                       ``LANDMARK_AUTO_MIN_ROWS``.

``backend="auto"`` scans the registry by priority and takes the first
backend whose ``auto_eligible`` accepts the problem; the
``REPRO_BACKEND`` environment variable replaces the *auto* default for
fleet-wide flips (an explicitly passed backend still wins, and an env
hint that names a backend unusable in the current mode degrades back to
the auto scan instead of failing).  ``interpret=None`` resolves in
``kernels.platform``: Pallas kernels compile on a TPU and run in the
interpreter only where no TPU exists (CI, laptops) — a TPU never falls
back to the interpreter on its own.

``donate=True`` routes through jit wrappers that donate the ``f0``
buffer — the streaming engine feeds freshly staged device arrays every
Δ_t and lets XLA recycle them in place rather than allocate per batch.
``compile_cache_size()`` sums the jit-cache entry count of every
registered backend's entry points (plus the sharded runners): the
streaming tests assert it stays ≤ the shape-bucket ladder size.
"""

from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.propagate import (PropagateResult, PropagationProblem,
                                  bsr_update_island, gather_rows, propagate)
from repro.kernels.bsr_spmv import (bsr_spmv, dense_to_bsr,  # noqa: F401
                                    ell_bsr_layout, fill_bsr_blocks)
from repro.kernels.cc_hook import cc_hook_step, connected_components_pallas  # noqa: F401
from repro.kernels.ell_propagate import ell_propagate_step
from repro.kernels.platform import on_tpu, resolve_interpret  # noqa: F401


# Below this row count the fused kernels' launch overhead beats the work
# saved; auto selection keeps such problems on the XLA reference path.
# Must exceed the 256-row bucket floor (core.snapshot.bucket): the count
# seen here is the padded one, so a smaller threshold would never fire.
_PALLAS_MIN_ROWS = 512

# The BSR tile edge and auto fill threshold are per-hardware registry
# properties now — see ``bsr_block_size`` / ``bsr_auto_fill_min`` below
# (8 interpret-friendly on CPU, the MXU's native 128 on real TPU).

# auto may pick the approximate landmark backend only at row counts
# where exact staging pressure is real — below this the whole problem
# fits a single exact rung comfortably and approximation buys nothing.
LANDMARK_AUTO_MIN_ROWS = 4096


# --------------------------------------------------------------------- #
# Backend registry
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ProblemInfo:
    """What auto-selection may know about a solve.

    ``block_fill`` is the post-component-reorder BSR fill factor — only
    the streaming engine measures it (at rung entry); plain callers leave
    it ``None``, which keeps ``bsr`` out of their auto scan.
    ``landmark_ready`` declares that the caller runs the hot/cold
    landmark machinery (sampled landmarks + assignment table); plain
    callers leave it False, which keeps the approximate ``landmark``
    backend out of their auto scan the same way.
    """

    num_rows: int | None = None
    block_fill: float | None = None
    sharded: bool = False
    landmark_ready: bool = False


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    """One propagation backend's declared capabilities."""

    name: str
    sharded: bool  # has a core.distributed per-shard update body
    transports: tuple[str, ...]  # collectives the sharded form supports
    auto_priority: int  # auto scans high → low
    auto_eligible: Callable[[ProblemInfo, str], bool]  # (info, hw) -> bool
    run: Callable  # single-device entry point
    cache_entry_points: tuple[Callable[[], object], ...]
    # per-hardware tile edge for backends that tile their aggregation
    # (hw string -> edge length); None for untiled backends
    block_size: Callable[[str], int] | None = None


_REGISTRY: dict[str, BackendSpec] = {}


def register_backend(spec: BackendSpec) -> BackendSpec:
    """Add a backend to the dispatch registry (last registration wins)."""
    _REGISTRY[spec.name] = spec
    return spec


def backend_names() -> tuple[str, ...]:
    """Registered backend names, registration order."""
    return tuple(_REGISTRY)


def backend_spec(name: str) -> BackendSpec:
    """The registered ``BackendSpec`` for ``name`` (raises on unknown)."""
    spec = _REGISTRY.get(name)
    if spec is None:
        raise ValueError(
            f"unknown backend {name!r}; want one of {backend_names()}")
    return spec


def bsr_block_size(hw: str | None = None) -> int:
    """The bsr backend's tile edge on ``hw`` (default: this process's
    backend) — a registry property, not a module constant: 8 keeps
    interpret-mode CI cheap while still mapping onto the MXU's (8, 128)
    lane tiling; on real TPU the (128, 128) MXU systolic array wants the
    full native edge."""
    return backend_spec("bsr").block_size(hw or jax.default_backend())


def bsr_auto_fill_min(hw: str | None = None) -> float:
    """Minimum touched-tile fill fraction for auto to pick bsr on ``hw``,
    re-derived from the tile edge: one (B, B) tile pays a fixed MXU pass
    regardless of how many of its entries carry a real edge, while the
    VPU ELL kernel pays per edge lane — so the break-even density scales
    as ~2/B (0.25 at the interpret-friendly edge of 8, ~0.016 at the MXU's
    128, where even sparse tiles amortize the systolic pass)."""
    return 2.0 / bsr_block_size(hw)


def _auto_select(info: ProblemInfo, hw: str) -> str:
    for spec in sorted(_REGISTRY.values(), key=lambda s: -s.auto_priority):
        if info.sharded and not spec.sharded:
            continue
        if spec.auto_eligible(info, hw):
            return spec.name
    raise RuntimeError("no auto-eligible backend registered")  # pragma: no cover


def select_backend(backend: str | None = None,
                   problem: PropagationProblem | None = None,
                   *,
                   num_rows: int | None = None,
                   sharded: bool = False,
                   block_fill: float | None = None,
                   landmark_ready: bool = False,
                   use_env: bool = True) -> str:
    """Resolve ``backend`` (None/"auto" → registry scan, env override).

    An explicit backend wins; the ``REPRO_BACKEND`` env var replaces the
    "auto" default; auto walks the registry by priority and takes the
    first backend whose ``auto_eligible`` accepts a ``ProblemInfo`` built
    from ``problem``/``num_rows``/``block_fill``.  An env *hint* naming a
    backend with no sharded form degrades to the auto scan when
    ``sharded`` (fleet-wide hints must not kill a stream); an explicitly
    passed backend reaches the caller's error path instead.

    ``use_env=False`` skips the env read — the streaming engine pins the
    hint once at construction (its row padding and candidate set depend
    on it), so a mid-stream env flip must not change later rungs.
    """
    if num_rows is None and problem is not None:
        num_rows = problem.num_unlabeled
    from_env = False
    if backend in (None, "auto"):
        env = (os.environ.get("REPRO_BACKEND", "auto") if use_env
               else "auto")
        from_env = env != "auto"
        backend = env
    info = ProblemInfo(num_rows=num_rows, block_fill=block_fill,
                       sharded=sharded, landmark_ready=landmark_ready)
    hw = jax.default_backend()
    if backend == "auto":
        return _auto_select(info, hw)
    spec = backend_spec(backend)
    if from_env and sharded and not spec.sharded:
        return _auto_select(info, hw)
    return backend


def backend_candidates(backend: str | None = None, *,
                       sharded: bool = False) -> tuple[str, ...]:
    """Every backend the given knob could resolve to, env included.

    The streaming engine asks this once at construction to decide
    whether BSR could ever be selected — and only then pays the
    block-size row padding and per-rung fill measurement.
    """
    if backend not in (None, "auto"):
        return (backend_spec(backend).name,)
    env = os.environ.get("REPRO_BACKEND", "auto")
    if env != "auto":
        spec = backend_spec(env)
        if not (sharded and not spec.sharded):
            return (env,)
    hw = jax.default_backend()
    optimistic = ProblemInfo(num_rows=None, block_fill=1.0, sharded=sharded,
                             landmark_ready=True)
    return tuple(
        s.name for s in sorted(_REGISTRY.values(),
                               key=lambda s: -s.auto_priority)
        if (not sharded or s.sharded) and s.auto_eligible(optimistic, hw))


# --------------------------------------------------------------------- #
# ell_pallas backend
# --------------------------------------------------------------------- #
def _pad_rows(problem: PropagationProblem, block_rows: int):
    n = problem.num_unlabeled
    pad = (-n) % block_rows
    if pad == 0:
        return problem, n
    padded = PropagationProblem(
        nbr=jnp.pad(problem.nbr, ((0, pad), (0, 0)), constant_values=-1),
        wgt=jnp.pad(problem.wgt, ((0, pad), (0, 0))),
        wl0=jnp.pad(problem.wl0, (0, pad)),
        wl1=jnp.pad(problem.wl1, (0, pad)),
        valid=jnp.pad(problem.valid, (0, pad)),
    )
    return padded, n


@functools.partial(jax.jit, static_argnames=("max_iters", "block_rows", "interpret"))
def propagate_pallas(
    problem: PropagationProblem,
    f0: jax.Array,
    frontier0: jax.Array,
    delta: float = 1e-4,
    max_iters: int = 100_000,
    block_rows: int = 512,
    interpret: bool | None = None,
) -> PropagateResult:
    """Frontier propagation loop driven by the fused Pallas kernel."""
    interpret = resolve_interpret(interpret)
    problem, n_orig = _pad_rows(problem, block_rows)
    n = problem.num_unlabeled
    f0 = jnp.pad(f0.astype(jnp.float32), (0, n - n_orig))
    frontier0 = jnp.pad(frontier0, (0, n - n_orig)) & problem.valid

    mask = problem.nbr >= 0
    idx = jnp.where(mask, problem.nbr, 0)

    def cond(state):
        """Sweep while the frontier is non-empty and iterations remain."""
        _, frontier, it, _ = state
        return jnp.logical_and(frontier.any(), it < max_iters)

    def body(state):
        """One frontier-masked Jacobi sweep; returns the next state."""
        f, frontier, it, _ = state
        f_new, changed = ell_propagate_step(
            problem.nbr, problem.wgt, problem.wl0, problem.wl1,
            frontier, f, delta=delta, block_rows=block_rows,
            interpret=interpret,
        )
        changed &= problem.valid
        nbr_changed = jnp.any(gather_rows(changed, idx) & mask, axis=1)
        new_frontier = (changed | nbr_changed) & problem.valid
        resid = jnp.max(jnp.abs(f_new - f), initial=0.0)
        return f_new, new_frontier, it + 1, resid

    f, frontier, iters, resid = jax.lax.while_loop(
        cond, body, (f0, frontier0, jnp.int32(0), jnp.float32(0)))
    return PropagateResult(
        f=f[:n_orig], iterations=iters, converged=~frontier.any(),
        max_residual=resid)


# --------------------------------------------------------------------- #
# BSR / MXU backend — tiles built directly from the ELL tensor
# --------------------------------------------------------------------- #
def _bsr_fixpoint(problem, slot, f0, frontier0, delta, max_iters, interpret,
                  block_size, num_slots):
    """Frontier fixpoint with the aggregation as a BSR SpMV.  The tile
    tensor is scatter-built from the staged ELL arrays *inside* the jit
    (``fill_bsr_blocks``), so it never exists on the host."""
    nbr = problem.nbr
    blocks, bcols = fill_bsr_blocks(nbr, problem.wgt, slot,
                                    block_size=block_size,
                                    num_slots=num_slots)
    mask = nbr >= 0
    idx = jnp.where(mask, nbr, 0)
    delta_ = jnp.asarray(delta, jnp.float32)
    wall = problem.wall()
    valid = problem.valid
    n = nbr.shape[0]

    def cond(state):
        """Sweep while the frontier is non-empty and iterations remain."""
        _, frontier, it, _ = state
        return jnp.logical_and(frontier.any(), it < max_iters)

    def body(state):
        """One frontier-masked Jacobi sweep; returns the next state."""
        f, frontier, it, _ = state
        # F'_u = (Σ_v w(u,v)·F_v + wl1_u) / Wall_u — §5's weighted average,
        # with the neighbor sum as a block-sparse matvec on the MXU.
        y = bsr_spmv(blocks, bcols, f, interpret=interpret)[:n]
        f_all = bsr_update_island(y, problem.wl1, wall, f)
        f_new = jnp.where(frontier & valid, f_all, f)
        resid = jnp.abs(f_new - f)
        changed = (resid > delta_) & valid
        nbr_changed = jnp.any(gather_rows(changed, idx) & mask, axis=1)
        new_frontier = (changed | nbr_changed) & valid
        return f_new, new_frontier, it + 1, jnp.max(resid, initial=0.0)

    f, frontier, iters, resid = jax.lax.while_loop(
        cond, body, (f0.astype(jnp.float32), frontier0 & valid,
                     jnp.int32(0), jnp.float32(0)))
    return PropagateResult(
        f=f, iterations=iters, converged=~frontier.any(), max_residual=resid)


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret",
                                             "block_size", "num_slots"))
def _bsr_solve(problem, slot, f0, frontier0, delta, max_iters, interpret,
               block_size, num_slots):
    return _bsr_fixpoint(problem, slot, f0, frontier0, delta, max_iters,
                         interpret, block_size, num_slots)


@functools.partial(jax.jit, static_argnames=("max_iters", "interpret",
                                             "block_size", "num_slots"),
                   donate_argnums=(2,))
def _bsr_donating(problem, slot, f0, frontier0, delta, max_iters, interpret,
                  block_size, num_slots):
    return _bsr_fixpoint(problem, slot, f0, frontier0, delta, max_iters,
                         interpret, block_size, num_slots)


def propagate_bsr(
    problem: PropagationProblem,
    f0: jax.Array,
    frontier0: jax.Array,
    delta: float = 1e-4,
    max_iters: int = 100_000,
    block_size: int | None = None,
    interpret: bool | None = None,
    slot=None,
    num_slots: int | None = None,
    donate: bool = False,
) -> PropagateResult:
    """Frontier propagation with the aggregation as a BSR SpMV (MXU path).

    Streaming callers (``core.stream.StreamEngine``) pass a pre-ordered
    problem plus the per-edge ``slot`` map and the rung's compiled
    ``num_slots`` budget (``kernels.bsr_spmv.ell_bsr_layout``).  One-shot
    callers pass neither: this entry point then component-reorders the
    rows on the host (the paper's Step-1 clustering order), derives the
    layout in O(nnz), solves in the reordered space, and folds the labels
    back — no dense (U, U) intermediate at any size.
    """
    interpret = resolve_interpret(interpret)
    if block_size is None:
        block_size = bsr_block_size()
    if slot is not None:
        if num_slots is None:
            raise ValueError("propagate_bsr with slot= needs num_slots= "
                             "(the compiled tile-slot budget)")
        if isinstance(slot, np.ndarray) and slot.size \
                and int(slot.max()) >= num_slots:
            # a slot beyond the budget would scatter into a neighboring
            # block row's tile — refuse loudly instead (device-array
            # callers rely on fill_bsr_blocks dropping such lanes; the
            # streaming engine checks its budget before dispatch)
            raise ValueError(
                f"slot map needs {int(slot.max()) + 1} tile slots but "
                f"num_slots={num_slots}; pass the layout's num_slots "
                "(padded up is fine)")
        fn = _bsr_donating if donate else _bsr_solve
        return fn(problem, jnp.asarray(slot), f0, frontier0, delta,
                  max_iters=max_iters, interpret=interpret,
                  block_size=block_size, num_slots=num_slots)

    # one-shot path: reorder + layout on the host, O(nnz).  Deferred
    # imports: repro.core's package init reaches back into this module
    # (dynlp), so core submodules beyond `propagate` can't load at import
    # time here.
    from repro.core.components import component_order, permute_ell_rows
    from repro.core.snapshot import bucket_k

    n = problem.num_unlabeled
    pad = (-n) % block_size
    nbr_h = np.asarray(problem.nbr)
    if pad:
        nbr_h = np.concatenate(
            [nbr_h, np.full((pad, nbr_h.shape[1]), -1, np.int32)])
    order = component_order(nbr_h)
    nbr_p, inv = permute_ell_rows(nbr_h, order)
    layout = ell_bsr_layout(nbr_p, block_size)

    def rpad(x, fill=0):
        """Pad per-row arrays to the block multiple, then permute."""
        x = np.asarray(x)
        if not pad:
            return x[order]
        widths = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
        return np.pad(x, widths, constant_values=fill)[order]

    pp = PropagationProblem(
        nbr=jnp.asarray(nbr_p), wgt=jnp.asarray(rpad(problem.wgt)),
        wl0=jnp.asarray(rpad(problem.wl0)), wl1=jnp.asarray(rpad(problem.wl1)),
        valid=jnp.asarray(rpad(problem.valid, False)))
    res = _bsr_solve(
        pp, jnp.asarray(layout.slot),
        jnp.asarray(rpad(np.asarray(f0, np.float32))),
        jnp.asarray(rpad(np.asarray(frontier0), False)),
        delta, max_iters=max_iters, interpret=interpret,
        block_size=block_size, num_slots=bucket_k(layout.num_slots))
    return PropagateResult(
        f=res.f[jnp.asarray(inv[:n])], iterations=res.iterations,
        converged=res.converged, max_residual=res.max_residual)


# --------------------------------------------------------------------- #
# Donating wrappers (streaming path): the f0 buffer is consumed and its
# storage recycled by XLA across Δ_t.  (frontier0 stays undonated: its
# bool[U] shape has no matching output to alias.)
# --------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("max_iters",),
                   donate_argnums=(1,))
def _ref_donating(problem, f0, frontier0, delta, max_iters):
    return propagate(problem, f0, frontier0, delta=delta, max_iters=max_iters)


@functools.partial(jax.jit,
                   static_argnames=("max_iters", "block_rows", "interpret"),
                   donate_argnums=(1,))
def _pallas_donating(problem, f0, frontier0, delta, max_iters, block_rows,
                     interpret):
    return propagate_pallas(problem, f0, frontier0, delta=delta,
                            max_iters=max_iters, block_rows=block_rows,
                            interpret=interpret)


# --------------------------------------------------------------------- #
# Registry entries (scan order for auto = priority, high first)
# --------------------------------------------------------------------- #
def _run_ref(problem, f0, frontier0, *, delta, max_iters, donate, **_):
    if donate:
        return _ref_donating(problem, f0, frontier0, delta, max_iters)
    return propagate(problem, f0, frontier0, delta=delta,
                     max_iters=max_iters)


def _run_ell_pallas(problem, f0, frontier0, *, delta, max_iters, block_rows,
                    interpret, donate, **_):
    interpret = resolve_interpret(interpret)
    block_rows = min(block_rows, problem.num_unlabeled)
    if donate:
        return _pallas_donating(problem, f0, frontier0, delta, max_iters,
                                block_rows, interpret)
    return propagate_pallas(problem, f0, frontier0, delta=delta,
                            max_iters=max_iters, block_rows=block_rows,
                            interpret=interpret)


def _run_bsr(problem, f0, frontier0, *, delta, max_iters, interpret, donate,
             slot=None, num_slots=None, block_size=None, **_):
    return propagate_bsr(problem, f0, frontier0, delta=delta,
                         max_iters=max_iters, block_size=block_size,
                         interpret=interpret, slot=slot, num_slots=num_slots,
                         donate=donate)


def _run_landmark(problem, f0, frontier0, *, delta, max_iters, donate, **_):
    """The landmark backend's solve body — the exact reference update.

    The approximation lives entirely in how the streaming engine STAGES
    for this backend (hot-restricted snapshot with cold labels folded as
    boundary weights, plus the commit-time low-rank cold pass in
    ``kernels.landmark_propagate``).  The staged problem itself is solved
    exactly, so standalone callers selecting ``landmark`` just get the
    reference answer.
    """
    return _run_ref(problem, f0, frontier0, delta=delta,
                    max_iters=max_iters, donate=donate)


def _landmark_cold_entry():
    # deferred: landmark_propagate imports argkmin, which this module's
    # importers don't all need at import time
    from repro.kernels.landmark_propagate import _cold_pass
    return _cold_pass


register_backend(BackendSpec(
    name="ref",
    sharded=True,
    transports=("allgather", "halo"),
    auto_priority=10,  # the always-eligible floor of the scan
    auto_eligible=lambda info, hw: True,
    run=_run_ref,
    cache_entry_points=(lambda: propagate, lambda: _ref_donating),
))

register_backend(BackendSpec(
    name="ell_pallas",
    sharded=True,
    transports=("allgather", "halo"),
    auto_priority=20,
    # never auto: the kernel gathers F from VMEM by neighbor id, which
    # Mosaic refuses ("Only 2D gather is supported"), so on a TPU it does
    # not compile and off-TPU it would only ever run interpreted
    auto_eligible=lambda info, hw: False,
    run=_run_ell_pallas,
    cache_entry_points=(lambda: propagate_pallas, lambda: _pallas_donating),
))

register_backend(BackendSpec(
    name="bsr",
    sharded=True,
    transports=("allgather", "halo"),
    auto_priority=30,  # MXU path outranks the VPU kernel when eligible
    auto_eligible=lambda info, hw: hw == "tpu"
    and info.block_fill is not None
    and info.block_fill >= bsr_auto_fill_min(hw)
    and (info.num_rows is None or info.num_rows >= _PALLAS_MIN_ROWS),
    run=_run_bsr,
    cache_entry_points=(lambda: _bsr_solve, lambda: _bsr_donating),
    block_size=lambda hw: 128 if hw == "tpu" else 8,
))

register_backend(BackendSpec(
    name="landmark",
    sharded=True,  # the hot solve reuses the ref mesh body + transports
    transports=("allgather", "halo"),
    auto_priority=40,  # when the caller runs hot/cold, scale wins
    auto_eligible=lambda info, hw: info.landmark_ready and (
        info.num_rows is None or info.num_rows >= LANDMARK_AUTO_MIN_ROWS),
    run=_run_landmark,
    cache_entry_points=(lambda: propagate, lambda: _ref_donating,
                        _landmark_cold_entry),
))

BACKENDS = backend_names()


def run_propagation(
    problem: PropagationProblem,
    f0: jax.Array,
    frontier0: jax.Array,
    *,
    delta: float | jax.Array = 1e-4,
    max_iters: int = 100_000,
    backend: str | None = None,
    block_rows: int = 512,
    interpret: bool | None = None,
    donate: bool = False,
    mesh: jax.sharding.Mesh | None = None,
    shard_plan=None,
    transport: str | None = None,
    export_max: int | None = None,
    slot=None,
    num_slots: int | None = None,
    block_size: int | None = None,
) -> PropagateResult:
    """Single propagation entry point — see module docstring for routing.

    ``mesh`` adds the distributed arm: the selected backend's update body
    is wrapped in the vertex-partitioned ``shard_map`` transport of
    ``core.distributed`` (rows sharded over every mesh axis, one
    collective per sweep).  ``transport`` selects that collective:
    ``"allgather"`` (default) ships full F blocks and is layout-free;
    ``"halo"`` ships only per-shard export prefixes of length
    ``export_max`` and requires the problem's rows to already sit in a
    halo export-prefix layout (``graph.partition.build_halo_plan`` /
    ``core.snapshot.apply_halo_layout``) — labels are bit-identical
    either way.  Requires ``problem``'s row count to be a multiple of the
    mesh's device count.  Callers that stream many batches pass a
    prebuilt ``shard_plan`` (one per bucket rung; ``StreamShardPlan`` or
    ``StreamHaloPlan``, which then fixes the transport) so partition
    planning isn't redone per Δ_t; otherwise the plan is resolved (and
    memoized) from ``mesh`` + the problem shape.  The ``bsr`` backend
    additionally needs the per-edge ``slot`` map and (sharded) the
    compiled ``num_slots`` budget — ``StreamEngine`` derives both per
    Δ_t from ``kernels.bsr_spmv.ell_bsr_layout``.
    """
    sharded = mesh is not None or shard_plan is not None
    if transport not in (None, "allgather", "halo"):
        raise ValueError(f"unknown transport {transport!r}; "
                         "want 'allgather' or 'halo'")
    if transport == "halo" and not sharded:
        raise ValueError("transport='halo' needs mesh= or a shard_plan "
                         "(single-device solves have no collective)")
    backend = select_backend(backend, problem, sharded=sharded)
    spec = backend_spec(backend)
    if sharded:
        from repro.core import distributed

        if not spec.sharded:
            raise ValueError(
                f"backend {backend!r} is single-device only; registry "
                f"sharded backends: "
                f"{tuple(s.name for s in _REGISTRY.values() if s.sharded)}")
        if transport is not None and transport not in spec.transports:
            raise ValueError(
                f"backend {backend!r} does not support transport "
                f"{transport!r}; declared transports: {spec.transports}")
        plan = shard_plan
        if plan is None:
            bsr_kw = {}
            if backend == "bsr":
                if slot is None or num_slots is None:
                    raise ValueError(
                        "sharded backend='bsr' needs slot= and num_slots= "
                        "(the per-edge BSR slot map + compiled tile budget "
                        "from kernels.bsr_spmv.ell_bsr_layout)")
                bsr_kw = dict(
                    block_size=(block_size if block_size is not None
                                else bsr_block_size()),
                    num_slots=num_slots)
            if transport == "halo":
                if export_max is None:
                    raise ValueError(
                        "transport='halo' without a shard_plan needs "
                        "export_max (the per-shard export-prefix length)")
                plan = distributed.build_stream_halo_plan(
                    mesh, tuple(problem.nbr.shape), export_max,
                    backend=backend, delta=float(delta),
                    max_iters=max_iters, block_rows=block_rows,
                    interpret=interpret, donate=donate, **bsr_kw)
            else:
                plan = distributed.build_stream_plan(
                    mesh, tuple(problem.nbr.shape), backend=backend,
                    delta=float(delta), max_iters=max_iters,
                    block_rows=block_rows, interpret=interpret,
                    donate=donate, **bsr_kw)
        else:
            # the plan's baked-in hyperparameters drive the solve — refuse
            # kwargs that silently disagree with them
            want = (backend, float(delta), max_iters, block_rows, interpret,
                    transport if transport is not None else plan.transport)
            have = (plan.backend, plan.delta, plan.max_iters,
                    plan.block_rows, plan.interpret, plan.transport)
            if want != have:
                raise ValueError(
                    f"shard_plan mismatch: called with (backend, delta, "
                    f"max_iters, block_rows, interpret, transport)={want} "
                    f"but plan was built with {have}")
            if backend == "bsr" and num_slots is not None \
                    and num_slots != plan.num_slots:
                raise ValueError(
                    f"shard_plan mismatch: num_slots={num_slots} but plan "
                    f"compiled {plan.num_slots}")
        if plan.backend == "bsr":
            if slot is None:
                raise ValueError("a bsr shard plan needs the per-edge "
                                 "slot map (slot=)")
            if isinstance(slot, np.ndarray) and slot.size \
                    and int(slot.max()) >= plan.num_slots:
                raise ValueError(
                    f"slot map needs {int(slot.max()) + 1} tile slots "
                    f"but the plan compiled num_slots={plan.num_slots}")
            return plan(problem, f0, frontier0, slot=jnp.asarray(slot))
        return plan(problem, f0, frontier0)
    return spec.run(problem, f0, frontier0, delta=delta, max_iters=max_iters,
                    block_rows=block_rows, interpret=interpret, donate=donate,
                    slot=slot, num_slots=num_slots, block_size=block_size)


def compile_cache_size() -> int:
    """Total jit-cache entries across every registered backend's entry
    points (plus the sharded shard_map runners).

    Each entry is one (shapes, statics) specialization, i.e. one compile.
    Sampled before/after a stream, the delta is the stream's recompile
    count — the number the bucket ladder is designed to bound.
    """
    total = 0
    seen: set[int] = set()
    for spec in _REGISTRY.values():
        for get in spec.cache_entry_points:
            fn = get()
            if id(fn) in seen:
                continue
            seen.add(id(fn))
            total += fn._cache_size()
    from repro.core import distributed

    return total + distributed.sharded_cache_size()
