"""Pallas TPU kernel: Shiloach–Vishkin hook + jump step (paper Fig. 2).

The CUDA version hooks each vertex to the min parent among its neighbors
and then pointer-jumps ``par[i] = par[par[i]]``.  The TPU version fuses both
into one pass over ELL row tiles with the parent vector VMEM-resident:
hook is a masked row min-reduce (VPU), jump is a second gather.

Like ``ell_propagate``, Mosaic refuses the in-kernel 1-D gathers ("Only 2D
gather is supported"), so this kernel runs interpreted off-TPU only; the
served path uses the XLA ``core.components.connected_components``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret


def _kernel(nbr_ref, par_ref, out_ref):
    nbr = nbr_ref[...]  # (R, K)
    par = par_ref[...]  # (N,)
    row0 = pl.program_id(0) * nbr.shape[0]
    rows = row0 + jax.lax.iota(jnp.int32, nbr.shape[0])
    own = par[rows]
    mask = nbr >= 0
    idx = jnp.where(mask, nbr, 0)
    nbr_par = jnp.take(par, idx.reshape(-1), axis=0).reshape(idx.shape)
    nbr_par = jnp.where(mask, nbr_par, jnp.iinfo(jnp.int32).max)
    hooked = jnp.minimum(own, jnp.min(nbr_par, axis=1))
    # jump (path halving): par[par[u]] — a second VMEM gather
    out_ref[...] = jnp.take(par, hooked, axis=0)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def cc_hook_step(
    nbr: jax.Array,  # (N, K) int32, PAD == -1
    par: jax.Array,  # (N,) int32
    block_rows: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    """One fused Shiloach–Vishkin hook + path-halving jump over the ELL
    adjacency: per row, min over the neighbors' parents, then one jump
    through the (previous iteration's) parent vector."""
    n, k = nbr.shape
    r = min(block_rows, n)
    assert n % r == 0
    out = pl.pallas_call(
        _kernel,
        grid=(n // r,),
        in_specs=[
            pl.BlockSpec((r, k), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((r,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(nbr, par)
    return out


def connected_components_pallas(nbr, max_iters: int = 10_000,
                                interpret: bool | None = None,
                                block_rows: int = 512):
    """Full SV loop built on the kernel (hook+jump until fixpoint).

    Note: the jump inside the fused kernel reads the PREVIOUS iteration's
    parent vector (Jacobi-style), which still converges to the same fixpoint
    as the sequential hook-then-jump (both are monotone min-contractions
    bounded by the true component min)."""

    n = nbr.shape[0]

    def cond(state):
        """Loop while any parent changed and iterations remain."""
        par, changed, it = state
        return jnp.logical_and(changed, it < max_iters)

    def body(state):
        """One hook+jump step; flags whether any parent moved."""
        par, _, it = state
        new = cc_hook_step(nbr, par, block_rows=block_rows, interpret=interpret)
        return new, jnp.any(new != par), it + 1

    par0 = jnp.arange(n, dtype=jnp.int32)
    par, _, iters = jax.lax.while_loop(cond, body, (par0, jnp.bool_(True), jnp.int32(0)))
    return par, iters
