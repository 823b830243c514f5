"""Iterative label propagation engines (paper Alg. 2 Step 3 and ITLP).

The device representation is a ``PropagationProblem`` over the *unlabeled*
vertices only: labeled classes are folded into per-node scalar weights
``wl0``/``wl1`` (the paper's supernode decomposition, §4 "Iterative
Propagation"), and the ELL neighbor list holds unlabeled-unlabeled edges.

The frontier ("affected set" V_aff) is a dense boolean mask; the queue-based
GPU frontier of the paper maps to mask + ``segment``-style scatter expansion
on TPU (DESIGN.md §2).  The whole dynamic update jits once via
``lax.while_loop``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.graph.structures import PAD


class PropagationProblem(NamedTuple):
    """Pytree describing one LP system over U unlabeled vertices.

    Attributes:
      nbr:   (U, K) int32 — unlabeled-neighbor ids (compact), PAD for empty.
      wgt:   (U, K) float32 — weights of those edges.
      wl0:   (U,) float32 — Σ w(u, v) over v ∈ L0 (class-0 supernode edge sum).
      wl1:   (U,) float32 — Σ w(u, v) over v ∈ L1.
      valid: (U,) bool — real rows (False for shard padding rows).
    """

    nbr: jax.Array
    wgt: jax.Array
    wl0: jax.Array
    wl1: jax.Array
    valid: jax.Array

    @property
    def num_unlabeled(self) -> int:
        return self.nbr.shape[0]

    def wall(self) -> jax.Array:
        """Total incident weight per node: unlabeled nbrs + label supernodes."""
        return jnp.sum(self.wgt, axis=1) + self.wl0 + self.wl1


def gather_rows(x: jax.Array, idx: jax.Array) -> jax.Array:
    """``x[idx]`` for an (U, K) index block, gathered in (K, U) order.

    Same values as ``x[idx]``.  The TPU compiler's time for the (U, K)
    form grows with the index count (about 17 s at 82,560 x 24, the
    ogbn-arxiv rung) while the lane-dense (K, U) form compiles in under a
    second; every rung of the ladder pays this once per program."""
    return x[idx.T].T


def _gather_labels(f: jax.Array, nbr: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Gather neighbor labels; returns (labels, slot_mask)."""
    mask = nbr != PAD
    idx = jnp.where(mask, nbr, 0)
    return gather_rows(f, idx), mask


def update_island(wgt, wl0, wl1, f, f_v, mask):
    """The per-row Jacobi arithmetic, isolated between optimization
    barriers so it compiles IDENTICALLY in every program that embeds it.

    XLA freely fuses this arithmetic with whatever surrounds it —
    all-gather collectives, halo scatter reconstructions, donation copies
    — and different fusion contexts can contract multiplies/adds (FMA)
    differently, shifting a row's update by 1 ULP.  A row whose |ΔF|
    straddles the δ threshold by that ULP then makes a different frontier
    decision, and the engines' bit-equality contract (single-device ≡
    all-gather ≡ halo, tests/test_stream_sharded.py) silently breaks.
    Barriering every operand and the result pins the island's HLO to one
    shape everywhere, so the contraction decision — whatever it is — is
    the same in all engines.  The barriers are no-copy identity ops at
    runtime; they only stop cross-boundary fusion.
    """
    wgt, wl0, wl1, f, f_v = jax.lax.optimization_barrier(
        (wgt, wl0, wl1, f, f_v))
    nbr_term = jnp.sum(wgt * jnp.where(mask, f_v - f[:, None], 0.0), axis=1)
    wall = jnp.sum(wgt, axis=1) + wl0 + wl1
    d_f = (0.0 - f) * wl0 + (1.0 - f) * wl1 + nbr_term
    fu = f + jnp.where(wall > 0, d_f / jnp.maximum(wall, 1e-30), 0.0)
    return jax.lax.optimization_barrier(fu)


def bsr_update_island(y, wl1, wall, f):
    """The BSR backend's per-row update, isolated like ``update_island``.

    ``y`` is the block-sparse neighbor aggregation Σ_v w(u,v)·F_v; the
    weighted-average form F' = (y + wl1)/Wall (paper §5) replaces the
    Jacobi-delta form because the MXU matvec produces the sum directly.
    Barriered for the same reason as ``update_island``: the sharded
    transports embed this arithmetic next to different collectives, and
    the bsr-allgather ≡ bsr-halo bit-equality contract needs XLA to emit
    it identically in both programs.
    """
    y, wl1, wall, f = jax.lax.optimization_barrier((y, wl1, wall, f))
    fu = jnp.where(wall > 0, (y + wl1) / jnp.maximum(wall, 1e-30), f)
    return jax.lax.optimization_barrier(fu)


def lp_update(problem: PropagationProblem, f: jax.Array) -> jax.Array:
    """One unmasked LP update for every row (paper Eq. in §4 / Alg.2 L28).

    F'_u = F_u + (0-F_u)·wl0/Wall + (1-F_u)·wl1/Wall + Σ_v (F_v-F_u)·w(u,v)/Wall
    which §5 proves equals the classic weighted neighborhood average.
    """
    nbr_f, mask = _gather_labels(f, problem.nbr)
    fu = update_island(problem.wgt, problem.wl0, problem.wl1, f, nbr_f, mask)
    return jnp.where(problem.valid, fu, f)


def _expand_frontier(problem: PropagationProblem, changed: jax.Array) -> jax.Array:
    """Neighbors of changed vertices join the frontier (Alg.2 L30).

    The graph is undirected (both edge directions are stored), so
    "neighbors of changed" equals "rows with a changed neighbor" — a gather
    with the same regular ELL access pattern as the label update, instead of
    the GPU-style scatter into a frontier queue."""
    mask = problem.nbr != PAD
    idx = jnp.where(mask, problem.nbr, 0)
    return jnp.any(gather_rows(changed, idx) & mask, axis=1)


class PropagateResult(NamedTuple):
    f: jax.Array
    iterations: jax.Array  # int32 scalar
    converged: jax.Array  # bool scalar
    max_residual: jax.Array  # float32 scalar: max |ΔF| at the final iteration


@functools.partial(jax.jit, static_argnames=("max_iters",))
def propagate(
    problem: PropagationProblem,
    f0: jax.Array,
    frontier0: jax.Array,
    delta: float | jax.Array = 1e-4,
    max_iters: int = 100_000,
) -> PropagateResult:
    """DynLP frontier-restricted propagation (Alg. 2 Step 3).

    Only frontier rows are *applied* each iteration; a row whose update moves
    more than ``delta`` keeps itself and enrolls its neighbors for the next
    iteration; otherwise it leaves the frontier.  Terminates when the frontier
    empties (or at ``max_iters``).
    """
    delta = jnp.asarray(delta, jnp.float32)

    def cond(state):
        _, frontier, it, _ = state
        return jnp.logical_and(frontier.any(), it < max_iters)

    def body(state):
        f, frontier, it, _ = state
        fu_all = lp_update(problem, f)
        fu = jnp.where(frontier, fu_all, f)
        resid = jnp.abs(fu - f)
        changed = resid > delta
        new_frontier = changed | _expand_frontier(problem, changed)
        new_frontier &= problem.valid
        return fu, new_frontier, it + 1, jnp.max(resid, initial=0.0)

    f, frontier, iters, resid = jax.lax.while_loop(
        cond, body, (f0, frontier0 & problem.valid, jnp.int32(0), jnp.float32(0))
    )
    return PropagateResult(
        f=f, iterations=iters, converged=~frontier.any(), max_residual=resid
    )


@functools.partial(jax.jit, static_argnames=("max_iters",))
def propagate_full(
    problem: PropagationProblem,
    f0: jax.Array,
    delta: float | jax.Array = 1e-4,
    max_iters: int = 100_000,
) -> PropagateResult:
    """ITLP: every unlabeled vertex updates every iteration; stop when the
    global max |ΔF| drops to ``delta`` (classic Zhu et al. iteration [40])."""
    delta = jnp.asarray(delta, jnp.float32)

    def cond(state):
        _, it, resid = state
        return jnp.logical_and(resid > delta, it < max_iters)

    def body(state):
        f, it, _ = state
        fu = lp_update(problem, f)
        return fu, it + 1, jnp.max(jnp.abs(fu - f), initial=0.0)

    f, iters, resid = jax.lax.while_loop(
        cond, body, (f0, jnp.int32(0), jnp.float32(jnp.inf))
    )
    return PropagateResult(
        f=f, iterations=iters, converged=resid <= delta, max_residual=resid
    )


def harmonic_residual(problem: PropagationProblem, f: jax.Array) -> jax.Array:
    """max_u |T(F)_u - F_u| — distance from the harmonic fixed point."""
    return jnp.max(jnp.abs(lp_update(problem, f) - f), initial=0.0)
