"""The benchmark's one traffic generator.

A traffic mix is a JSON file of parameters (``bench/traffic/<mix>.json``);
this module turns a mix, a configuration and ``--seed`` into the job
stream a run feeds to the system: a warm-up job, then the jobs of the
measured window, one after another.  Two services take jobs:

* ``campaign`` — a campaign through the campaign service: one routing
  algorithm, one traffic pattern, a fixed list of injection rates and
  ``seeds_per_rate`` lanes per rate; every job draws fresh lane seeds.
* ``control_plane`` — a controlled session of the quasi-static control
  plane: a run of ``cycles`` cycles from the configuration's base
  pattern, facing a seeded storm of link flaps, a region loss and
  traffic drifts.  The storm's shape is fixed by the mix, so its event
  cycles are the same in every session and only the links, hotspots and
  region drawn from the seed differ: the warm-up session runs every
  chunk length the control loop meets in the window.

The program receives only what this module makes: traffic matrices,
lane seeds and event schedules.  The storm composition is a copy of the
program's own chaos generator (``src/repro/noc/chaos.py``,
``chaos_schedule``).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from .ref.grid import Grid

SEED_MAX = 2**31 - 1     # lane and storm seeds: int32-safe


def pattern_matrix(grid: Grid, name: str) -> np.ndarray:
    """Normalized (N, N) traffic matrix of a synthetic pattern."""
    n = grid.n
    if name == "uniform":
        t = np.ones((n, n))
    elif name == "transpose":
        # (x_0, ..., x_{k-1}) -> (x_{k-1}, ..., x_0)
        if len(set(grid.dims)) != 1:
            raise ValueError("transpose needs every side equal")
        t = np.zeros((n, n))
        t[np.arange(n), grid.coords[:, ::-1] @ grid.strides] = 1.0
    else:
        raise ValueError(f"unknown traffic pattern {name!r}")
    np.fill_diagonal(t, 0.0)
    return t / t.sum()


def hotspot_matrix(n: int, rng, hotspots: int, weight: float = 8.0):
    """Uniform background with ``hotspots`` hot destination columns."""
    m = np.ones((n, n))
    m[:, rng.choice(n, size=min(hotspots, n), replace=False)] *= weight
    np.fill_diagonal(m, 0.0)
    return m / m.sum()


def undirected_links(channels: np.ndarray) -> list:
    seen, out = set(), []
    for u, v in channels:
        key = (min(int(u), int(v)), max(int(u), int(v)))
        if key not in seen:
            seen.add(key)
            out.append(key)
    return out


def region_links(grid: Grid, channels: np.ndarray, center: int,
                 radius: int) -> tuple:
    """Every directed link touching the nodes within Chebyshev
    ``radius`` of ``center`` (coordinates taken without wrap)."""
    c = grid.coords
    region = np.abs(c - c[center]).max(1) <= radius
    return tuple((int(u), int(v)) for u, v in channels
                 if region[u] or region[v])


@dataclasses.dataclass(frozen=True)
class Event:
    """One environment event of a session, before it becomes one of the
    program's event objects: ``kind`` is fail, recover or drift."""

    kind: str
    cycle: int
    links: tuple = ()
    traffic: np.ndarray | None = None


def storm(grid: Grid, channels, p: dict, cycles: int, epoch: int,
          rng) -> list[Event]:
    """A seeded storm, composed as the program's ``chaos_schedule``
    composes one: ``flap_storms`` storms of ``flap_bursts`` fail →
    recover rounds over ``flap_links`` links, ``drift_events`` hotspot
    drifts and ``region_failures`` region losses (each one control
    epoch after its slot), on evenly spaced slots of [start, horizon)."""
    links = undirected_links(channels)
    start, horizon = int(p["start"]), int(p["horizon"])
    if not 0 < start < horizon <= cycles:
        raise ValueError("storm must lie inside the session")
    total = p["flap_storms"] + p["drift_events"] + p["region_failures"]
    slots = iter(np.linspace(start, horizon, num=max(total, 1),
                             endpoint=False))
    events = []
    for _ in range(p["flap_storms"]):
        t0 = int(next(slots))
        pick = rng.choice(len(links), size=min(p["flap_links"], len(links)),
                          replace=False)
        flap = tuple(pair for i in pick for pair in
                     (links[i], (links[i][1], links[i][0])))
        for b in range(p["flap_bursts"]):
            t_fail = t0 + 2 * b * p["flap_period"]
            t_rec = t_fail + p["flap_period"]
            if t_rec >= horizon:
                break
            events += [Event("fail", max(t_fail, 1), flap),
                       Event("recover", t_rec, flap)]
    for _ in range(p["drift_events"]):
        events.append(Event("drift", max(int(next(slots)), 1),
                            traffic=hotspot_matrix(grid.n, rng,
                                                   p["drift_hotspots"])))
    for _ in range(p["region_failures"]):
        t0 = int(next(slots))
        center = int(rng.integers(grid.n))
        events.append(Event("fail", min(max(t0 + epoch, 1), horizon - 1),
                            region_links(grid, channels, center,
                                         p["region_radius"])))
    events.sort(key=lambda e: e.cycle)
    return events


def lane_seeds(rng, count: int) -> list[int]:
    return [int(s) for s in rng.integers(0, SEED_MAX, size=count)]


def jobs(mix: dict, config: dict, grid: Grid, seed: int):
    """The job stream: job 0 warms up, every later job is measured.

    Each job is a dict: ``index``, ``service``, ``rates`` and ``seeds``
    (lanes are every (rate, seed) pair), and for a control-plane session
    ``traffic`` (the base matrix) and ``events``.
    """
    channels = grid.channels()
    service = mix["service"]
    rng = np.random.default_rng([int(seed), 0x5EED])
    base = pattern_matrix(grid, mix["pattern"])
    index = 0
    while True:
        job = dict(index=index, service=service, traffic=base,
                   rates=list(mix["rates"]),
                   seeds=lane_seeds(rng, mix["seeds_per_rate"]))
        if service == "control_plane":
            job["events"] = storm(grid, channels, mix["storm"],
                                  config["cycles"], config["epoch"], rng)
        elif service != "campaign":
            raise ValueError(f"unknown service {service!r}")
        yield job
        index += 1
