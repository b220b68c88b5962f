"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time.

The window of a traced run is marked on the host by a
``jax.profiler.TraceAnnotation`` named :data:`WINDOW`; its start, read
on the host clock when it opened, puts the trace's clock and the
program's spans on one timeline.

* busy time — per device plane, the union of the intervals of its
  ``XLA Ops`` events inside the window, averaged over the devices;
* per-op and per-module device time — summed durations by op or module
  name, averaged over the devices, and per-op event counts over all
  devices;
* per device — its busy time and its three longest modules, to show
  which chips the work reached;
* idle gaps — the stretches of the window in which no op runs on the
  first device, each put down to the innermost host span that covers
  its middle.
"""

from __future__ import annotations

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def _union(intervals) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def _gaps(intervals, lo, hi):
    out, cur = [], lo
    for a, b in sorted(intervals):
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def op_name(text: str) -> str:
    """An op's name from its event name, which on a TPU is the whole HLO
    instruction: the instruction's name, and for a custom call (a Pallas
    kernel) its target, as ``%custom-call.3[tpu_custom_call]``."""
    head = text.split(" = ", 1)[0].strip()
    if "custom_call_target=" in text:
        target = text.split("custom_call_target=", 1)[1].split(",")[0]
        head += "[" + target.strip('"') + "]"
    return head


def device_planes(planes):
    """The accelerator planes: those with XLA op or module lines (a TPU
    trace also holds planes such as ``/device:CUSTOM:...`` without)."""
    return [p for p in planes if p.name.startswith("/device:")
            and any(ln.name in (OPS_LINE, MODULES_LINE) for ln in p.lines)]


def reduce(planes, window_unix_ns: float | None = None,
           host_spans=(), top: int = 10) -> dict:
    """Reduce the planes of one trace.

    ``window_unix_ns`` is the host-clock time at which the window
    annotation opened; ``host_spans`` are dicts with ``name``, ``ts`` and
    ``dur`` in host-clock microseconds.  Times in the result are seconds.
    """
    planes = list(planes)
    win = None
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            for e in ln.events:
                if e.name == WINDOW:
                    win = (e.start_ns, e.start_ns + e.duration_ns)
    if win is None:
        raise ValueError(f"the trace has no {WINDOW!r} annotation")
    lo, hi = win
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace has no device plane")
    busy, ops, modules, counts, first = [], {}, {}, {}, None
    by_device = []
    names = {}      # an op's event name is its whole HLO instruction
    for p in devs:
        iv, mods = [], {}
        for ln in p.lines:
            if ln.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in ln.events:
                a = max(e.start_ns, lo)
                b = min(e.start_ns + e.duration_ns, hi)
                if b <= a:
                    continue
                if ln.name == MODULES_LINE:
                    modules[e.name] = modules.get(e.name, 0.0) + (b - a)
                    mods[e.name] = mods.get(e.name, 0.0) + (b - a)
                    continue
                name = names.get(e.name)
                if name is None:
                    name = names[e.name] = op_name(e.name)
                ops[name] = ops.get(name, 0.0) + (b - a)
                iv.append((a, b))
                counts[name] = counts.get(name, 0) + 1
        busy.append(_union(iv))
        by_device.append([p.name, busy[-1] / 1e9, {
            k: v / 1e9 for k, v in sorted(mods.items(),
                                          key=lambda kv: -kv[1])[:3]}])
        if first is None:
            first = iv
    gaps = []
    offset = None if window_unix_ns is None else window_unix_ns - lo
    for a, b in _gaps(first or [], lo, hi):
        label = "no host span"
        if offset is not None:
            mid_us = (a + b) / 2 + offset
            mid_us /= 1e3
            inner = [s for s in host_spans
                     if s["ts"] <= mid_us <= s["ts"] + s["dur"]]
            if inner:
                label = min(inner, key=lambda s: s["dur"])["name"]
        gaps.append((label, (b - a) / 1e9, (a - lo) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    ndev = len(devs)
    return dict(
        window_s=(hi - lo) / 1e9,
        busy_s=sum(busy) / ndev / 1e9,
        devices=ndev,
        ops={k: v / ndev / 1e9 for k, v in ops.items()},
        modules={k: v / ndev / 1e9 for k, v in modules.items()},
        op_counts=counts,
        by_device=by_device,
        device_ops=[[k, v / ndev / 1e9] for k, v in
                    sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[k, v] for k, v, _ in gaps[:top]],
        gap_by_span=_sum_by(gaps),
        # where the longest gaps start, and the first device's last op,
        # in seconds from the window's start: an early last op means the
        # trace lost the end of the window
        gaps_at=[[k, v, at] for k, v, at in gaps[:3]],
        last_op_s=(max(b for _, b in first) - lo) / 1e9 if first else 0.0)


def _sum_by(gaps) -> dict:
    out = {}
    for k, v, _ in gaps:
        out[k] = out.get(k, 0.0) + v
    return out


def from_bytes(xspace: bytes):
    """The planes of a trace held in memory (a serialized XSpace)."""
    import jax
    return jax.profiler.ProfileData.from_serialized_xspace(xspace).planes
