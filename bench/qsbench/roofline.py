"""Operations and bytes of the planner's possibility pass, from its
shapes, whatever implements it.

The pass computes, for C "channels" (u, n), N sources s and N
destinations d,

    V[c, d] = Σ_s T[s, d] · [du[s, c] + offset + dn[c, d] == dist[s, d]]

which is one multiply and one add for each of the C·N·N triples (the
compare selects the term).  The least bytes it can move are its inputs
read once — du (N, C), dn (C, N), T and dist (N, N) — and V (C, N)
written once, four bytes an element.  The planner runs it once per plan
with C = N (the on-path traffic of every node, of which the channel
weights are a gather).
"""

from __future__ import annotations


def possibility_work(n: int, c: int) -> tuple[float, float]:
    """(operations, bytes) of one pass over C channels and N nodes."""
    ops = 2.0 * c * n * n
    nbytes = 4.0 * (n * c + c * n + 2 * n * n + c * n)
    return ops, nbytes


def least_time(ops: float, nbytes: float, peaks: dict) -> float:
    """The roofline's bound, in seconds: the larger of operations over
    the chip's peak rate and bytes over its memory bandwidth."""
    return max(ops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
