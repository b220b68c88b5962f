"""Fused flit-step kernel: the simulator's per-cycle hot path as one
pass (the fused body compiled by XLA; the Pallas kernels of the same
body run in interpret mode only), bit-identical to the unfused
``repro.noc.sim`` step it replaces."""

from .ops import (backend_supports_pallas, make_step, resolve_path,
                  state_footprint_bytes, vmem_budget_bytes)
from .ref import CORE_KEYS, make_cycle_fn, make_cycle_parts, split_rand

__all__ = ["backend_supports_pallas", "make_step", "resolve_path",
           "state_footprint_bytes", "vmem_budget_bytes", "make_cycle_fn",
           "make_cycle_parts", "split_rand", "CORE_KEYS"]
