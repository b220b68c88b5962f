"""Public op: the fused flit-step with backend-aware dispatch.

The fused cycle body runs compiled by XLA on every backend today: no
backend lowers it as a Pallas kernel (:func:`backend_supports_pallas`
says why), so the auto ladder resolves to the fused dense body and the
interpreter is never the default anywhere.  The ladder itself — the
whole-array kernel while the state fits the VMEM budget, else the
blocked node-tile grid (:mod:`.kernel`), else dense — stays in place for
a backend that can lower the kernels, and is tested with
``supported=True``.  Pass ``use_pallas`` / ``interpret`` explicitly (or
set ``SimConfig.sim_tile_nodes``) to pin a path; the differential
battery runs the Pallas kernels in interpret mode on CPU to keep them
covered.

Capacity math is DERIVED, not hand-maintained: the footprint the gate
compares against the budget comes from ``jax.eval_shape`` over the
actual initial state plus the abstract table shapes
(``repro.noc.sim.abstract_tables``), so a new state key (telemetry
rings, watchdog counters, whatever comes next) is counted the moment it
exists.  The budget itself is overridable (``SIMSTEP_VMEM_BUDGET`` env,
``--simstep-vmem-budget`` on the benchmark CLI), and every dispatch
decision is logged once per distinct (path, size, algo, tile), with the
reason for it, via :class:`repro.obs.log.EventLog` — set
``SIMSTEP_LOG=0`` to silence.

The entry point is :func:`make_step`: it returns a drop-in replacement
for the unfused ``repro.noc.sim._make_step`` transition — same
``step(tables, state, cycle) -> (state, None)`` contract, same state
pytree, bit-identical arrays — selected by ``SimConfig.use_kernel``.
"""

from __future__ import annotations

import math
import os
import sys

import jax

from repro.noc.simconfig import Algo, SimConfig
from repro.obs.log import EventLog
from .kernel import make_simstep_blocked, make_simstep_pallas
from .ref import (MOV_W, TABLE_TILE_AXES, make_cycle_fn, make_cycle_parts,
                  split_rand, tile_state_keys)


def backend_supports_pallas() -> bool:
    """Whether the fused cycle body lowers as a compiled Pallas kernel on
    this backend.  On none yet.  The body (``ref.py``) is generic jnp full
    of gathers and scatters.  Compiled for a TPU v5e, Mosaic stops the
    whole-array kernel with an ``AssertionError`` in
    ``_gather_lowering_rule``, and refuses the blocked kernel's node-tiled
    rank-1 blocks ("rank 1 block shapes ... multiple of the tiling size
    (128)"); the blocked tile body would then hit the same gather
    refusal.  Until the body is rewritten to lower, the ladder resolves
    to the fused body compiled by XLA, on TPU as on CPU."""
    return False


# Default on-chip budget (VMEM is ~16 MB/core on TPU, minus headroom for
# compiler scratch).  Override per run with SIMSTEP_VMEM_BUDGET.
VMEM_BUDGET_BYTES = 10 * 2**20


def vmem_budget_bytes() -> int:
    """The active on-chip budget: ``SIMSTEP_VMEM_BUDGET`` (bytes) when
    set, else :data:`VMEM_BUDGET_BYTES`."""
    env = os.environ.get("SIMSTEP_VMEM_BUDGET", "").strip()
    return int(env) if env else VMEM_BUDGET_BYTES


def _sizes(meta: dict, cfg: SimConfig):
    """(state shapes minus the PRNG key, abstract tables) — the traced
    operands of one simulation cell, as ShapeDtypeStructs.  eval_shape
    stages ``fresh_state`` without allocating anything."""
    from repro.noc import sim  # deferred: sim dispatches back into us
    state = dict(jax.eval_shape(lambda: sim.fresh_state(meta, cfg)))
    state.pop("key")  # advanced outside the kernel
    return state, sim.abstract_tables(meta)


def _nbytes(spec) -> int:
    return math.prod(spec.shape) * spec.dtype.itemsize


def state_footprint_bytes(meta: dict, cfg: SimConfig) -> int:
    """Bytes the whole-array kernel must hold on chip: the full state
    pytree (PRNG key excluded) plus the traced tables — derived from
    the real array shapes, never a parallel formula."""
    state, tables = _sizes(meta, cfg)
    return (sum(_nbytes(s) for s in state.values())
            + sum(_nbytes(s) for s in tables))


def blocked_tile_bytes(meta: dict, cfg: SimConfig, tile_nodes: int) -> int:
    """Estimated on-chip bytes for one grid step of the blocked kernel
    at ``tile_nodes`` nodes per tile: double-buffered tile blocks
    (state slices in+out, table/rand slices in, the ``mov`` halo out)
    plus the whole-array residents (coords, channel tables, the
    ``fs_pre`` snapshot).  Derived from the same eval_shape sizes as
    :func:`state_footprint_bytes`."""
    state, tables = _sizes(meta, cfg)
    n, nin = meta["N"], meta["NIN"]
    pv = meta["P"] * meta["V"]
    tn = tile_nodes
    nin_t = tn * pv
    node_keys, input_keys, _scalars = tile_state_keys(cfg)
    streamed = resident = 0
    for field, spec in zip(tables._fields, tables):
        ax = TABLE_TILE_AXES[field]
        if ax is None:
            resident += _nbytes(spec)
        else:
            kind, axis = ax
            size = tn if kind == "node" else nin_t
            frac = size / spec.shape[axis]
            streamed += int(_nbytes(spec) * frac)
    for k in node_keys:
        streamed += 2 * _nbytes(state[k]) * tn // n      # in + out
    for k in input_keys:
        streamed += 2 * _nbytes(state[k]) * nin_t // nin  # in + out
    streamed += tn * 4 * (2 + max(meta["NDIM"], 1))  # rand draws
    streamed += tn * meta["P"] * MOV_W * 4           # mov halo out
    resident += nin * 4                              # fs_pre snapshot
    return 2 * streamed + resident  # ×2: grid-pipeline double buffering


def auto_tile_nodes(meta: dict, cfg: SimConfig,
                    budget: int | None = None) -> int:
    """Largest node-tile size that divides the network and fits the
    blocked kernel's per-step budget; 0 when no tile fits (the caller
    then falls back to the dense body)."""
    budget = vmem_budget_bytes() if budget is None else budget
    n = meta["N"]
    for tn in sorted((d for d in range(1, n + 1) if n % d == 0),
                     reverse=True):
        if blocked_tile_bytes(meta, cfg, tn) <= budget:
            return tn
    return 0


def resolve_path(meta: dict, cfg: SimConfig,
                 use_pallas: bool | None = None,
                 interpret: bool | None = None,
                 supported: bool | None = None,
                 budget: int | None = None) -> tuple[str, int, bool]:
    """The dispatch ladder: ``(path, tile_nodes, interpret)`` with
    ``path`` one of ``"whole"`` / ``"blocked"`` / ``"dense"``.

    * ``use_pallas=False`` pins the fused dense body.
    * ``cfg.sim_tile_nodes > 0`` pins the blocked kernel at that tile.
    * ``use_pallas=True`` pins the whole-array kernel.
    * auto (all ``None``/0): where Pallas lowers (``supported``, by
      default :func:`backend_supports_pallas`), whole-array while the
      state fits the budget, else the largest fitting tile, else dense;
      elsewhere, dense.

    ``interpret`` resolves to compiled where supported; forcing a
    Pallas path elsewhere runs the interpreter for the whole-array
    kernel, while the blocked path prefers its compiled ``vmap`` flavor
    unless ``interpret=True`` asks for the Pallas interpreter explicitly.
    """
    supported = (backend_supports_pallas() if supported is None
                 else supported)
    budget = vmem_budget_bytes() if budget is None else budget
    tile = int(getattr(cfg, "sim_tile_nodes", 0))
    if use_pallas is False:
        return "dense", 0, False
    if tile > 0:
        return "blocked", tile, bool(interpret) and not supported
    if use_pallas:
        interp = (interpret if interpret is not None else not supported)
        return "whole", 0, bool(interp)
    if not supported:
        return "dense", 0, False
    if state_footprint_bytes(meta, cfg) <= budget:
        return "whole", 0, False
    tile = auto_tile_nodes(meta, cfg, budget)
    if tile:
        return "blocked", tile, False
    return "dense", 0, False


# Dispatch decisions are diagnosable from the job log: one line per
# distinct (path, nodes, algo, tile) on stderr unless SIMSTEP_LOG=0.
_LOG = EventLog(
    verbose=os.environ.get("SIMSTEP_LOG", "1").lower()
    not in ("0", "false", "off"),
    stream=sys.stderr)
_LOGGED: set = set()


def _dispatch_reason(path: str, cfg: SimConfig,
                     use_pallas: bool | None) -> str:
    """Why :func:`resolve_path` took ``path``, for the dispatch event."""
    if use_pallas is False:
        return "pinned: use_pallas=False"
    if int(getattr(cfg, "sim_tile_nodes", 0)) > 0:
        return "pinned: sim_tile_nodes"
    if use_pallas:
        return "pinned: use_pallas=True"
    if not backend_supports_pallas():
        return (f"no compiled Pallas lowering of the cycle body on "
                f"{jax.default_backend()}")
    return {"whole": "state fits the VMEM budget",
            "blocked": "largest node tile in budget",
            "dense": "no node tile fits the VMEM budget"}[path]


def _log_dispatch(path: str, meta: dict, cfg: SimConfig, tile: int,
                  interpret: bool, use_pallas: bool | None) -> None:
    key = (path, meta["N"], int(cfg.algo), tile, bool(interpret))
    if key in _LOGGED:
        return
    _LOGGED.add(key)
    _LOG.event("simstep_dispatch", cat="kernel", path=path,
               nodes=meta["N"], algo=Algo(cfg.algo).name,
               tile_nodes=tile, interpret=bool(interpret),
               reason=_dispatch_reason(path, cfg, use_pallas),
               footprint_bytes=state_footprint_bytes(meta, cfg),
               budget_bytes=vmem_budget_bytes())


def make_step(meta: dict, cfg: SimConfig,
              use_pallas: bool | None = None,
              interpret: bool | None = None):
    """Build the fused per-cycle transition for one simulation cell.

    Path selection is :func:`resolve_path` (whole-array Pallas /
    blocked Pallas / fused dense, by backend, footprint and
    ``cfg.sim_tile_nodes``); the decision is logged via
    :mod:`repro.obs.log`.  All paths are bit-identical — forcing one
    can change the op schedule, never a result.
    """
    path, tile, interp = resolve_path(meta, cfg, use_pallas, interpret)
    if path == "whole":
        run_cycle = make_simstep_pallas(make_cycle_fn(meta, cfg),
                                        interpret=interp)
    elif path == "blocked":
        tile_fn, finish_fn = make_cycle_parts(meta, cfg)
        compiled = backend_supports_pallas()
        flavor = "pallas" if (compiled or interp) else "xla"
        run_cycle = make_simstep_blocked(
            meta, cfg, tile_fn, finish_fn, tile, flavor=flavor,
            interpret=interp and not compiled)
    else:
        run_cycle = make_cycle_fn(meta, cfg)
    _log_dispatch(path, meta, cfg, tile, interp, use_pallas)
    algo = Algo(cfg.algo)
    n, ndim = meta["N"], meta["NDIM"]

    def step(tables, state, cycle):
        # PRNG advance stays outside the kernel (no key ops in Pallas);
        # split_rand consumes the key exactly like the unfused step, so
        # the streams stay aligned cycle for cycle.
        key, rand = split_rand(state["key"], algo, n, ndim)
        core = {k: v for k, v in state.items() if k != "key"}
        core = run_cycle(tables, core, rand, cycle)
        core["key"] = key
        return core, None

    return step
