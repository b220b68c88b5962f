"""Pallas TPU kernels: N-Rank possibility weights (the O(C·N²) hot spot).

One kernel computes the per-destination possibility traffic

    V[c, d] = Σ_s T[s,d]·[du[s,c] + offset + dn[c,d] == dist[s,d]]

consumed by the fused planning pipeline (:mod:`repro.core.plan_fast`):
W is its row sum, W_drn its ``d = n`` gather, and the consecutive-channel
joint possibility a cheap O(P·N) contraction of it.
``possibility_weights_pallas`` — the classic (W, W_drn) reduction of
eq. 5/7 — is that row sum plus the O(N·C) draining term.

Blocking: grid (channel blocks, destination blocks, source blocks), with
the sources reduced into the output block, which stays in VMEM across the
source axis because its index map ignores it.  Per grid step the
(BC, BS, BD) mask is a broadcast compare followed by a multiply with T
and a sum over ``s`` — plain VPU work that Mosaic lowers, with no batched
contraction.  The destination axis keeps the mask at 2 MiB whatever the
network size; without it the (BC, BS, N) mask of a 64×64 mesh needs
256 MiB.

``offset`` generalizes the minimal-path predicate to k-hop continuations
(``offset=1`` is eq. 4/5; ``offset=2`` the consecutive-pair predicate).
``interpret`` defaults to False — the compiled path; CPU callers (no
Pallas backend) must opt into interpret mode explicitly, which
``repro.kernels.possibility.ops`` does automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Destinations per grid step: one lane-width of the (BC, BD) output block.
_BLOCK_D = 128


def _v_kernel(du_ref, dn_ref, t_ref, dist_ref, v_ref, *, offset: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        v_ref[...] = jnp.zeros_like(v_ref)

    du = du_ref[...]                                   # (BS, BC)
    lhs = du.T[:, :, None] + offset + dn_ref[...][:, None, :]  # (BC,BS,BD)
    mask = (lhs == dist_ref[...][None]).astype(t_ref.dtype)
    v_ref[...] += jnp.sum(mask * t_ref[...][None], axis=1)


def _pad_to(x, rows: int, cols: int):
    pr, pc = (-x.shape[0]) % rows, (-x.shape[1]) % cols
    return jnp.pad(x, ((0, pr), (0, pc))) if pr or pc else x


@functools.partial(jax.jit, static_argnames=("block_c", "block_s",
                                             "offset", "interpret"))
def possibility_v_pallas(du, dn, traffic, dist,
                         block_c: int = 128, block_s: int = 32,
                         offset: int = 1, interpret: bool = False):
    """Per-destination possibility traffic V (C, N):
    ``V[c, d] = Σ_s T[s,d]·[du[s,c] + offset + dn[c,d] == dist[s,d]]``.

    Operands are zero-padded up to whole blocks: padded sources carry
    zero traffic, and padded channels and destinations are sliced off.
    """
    n, c = du.shape
    bc, bs, bd = min(block_c, c), min(block_s, n), min(_BLOCK_D, n)
    du = _pad_to(du, bs, bc)
    dn = _pad_to(dn, bc, bd)
    traffic = _pad_to(traffic, bs, bd)
    dist = _pad_to(dist, bs, bd)
    grid = (dn.shape[0] // bc, dn.shape[1] // bd, du.shape[0] // bs)
    v = pl.pallas_call(
        functools.partial(_v_kernel, offset=offset),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bs, bc), lambda cb, db, sb: (sb, cb)),  # du
            pl.BlockSpec((bc, bd), lambda cb, db, sb: (cb, db)),  # dn
            pl.BlockSpec((bs, bd), lambda cb, db, sb: (sb, db)),  # traffic
            pl.BlockSpec((bs, bd), lambda cb, db, sb: (sb, db)),  # dist
        ],
        out_specs=pl.BlockSpec((bc, bd), lambda cb, db, sb: (cb, db)),
        out_shape=jax.ShapeDtypeStruct(dn.shape, traffic.dtype),
        interpret=interpret,
    )(du, dn, traffic, dist)
    return v[:c, :n]


@functools.partial(jax.jit, static_argnames=("block_c", "block_s",
                                             "offset", "interpret"))
def possibility_weights_pallas(du, dn, dsn, tn, traffic, dist,
                               block_c: int = 128, block_s: int = 32,
                               offset: int = 1,
                               interpret: bool = False):
    """(W, W_drn) per channel: W is the row sum of
    :func:`possibility_v_pallas`, W_drn the O(N·C) draining term."""
    v = possibility_v_pallas(du, dn, traffic, dist, block_c=block_c,
                             block_s=block_s, offset=offset,
                             interpret=interpret)
    drn = ((du + offset) == dsn).astype(traffic.dtype)
    return v.sum(1), jnp.sum(drn * tn, axis=0)
