"""Structured trace events: Chrome trace-event JSON, Perfetto-viewable.

The flight recorder's host-side plane.  :class:`TraceWriter` streams
events to disk in the Chrome trace-event **JSON Array Format**: a ``[``
followed by one ``{event},`` per line.  The format explicitly allows
the closing ``]`` to be absent, so a stream killed mid-write (the
campaign service's whole threat model) is still loadable by Perfetto /
``chrome://tracing`` — the writer therefore *never* terminates the
array, and resume simply appends.

Event vocabulary (the ``ph`` phases used here):

* ``X`` *complete* — a span with ``ts`` + ``dur`` (host wall time of a
  plan build, a control epoch, a campaign cell);
* ``i`` *instant* — a point event (drift detection, table hot-swap,
  link fail/recover, plan-cache hit/miss);
* ``C`` *counter* — a named value series (drift TV-distance per epoch,
  cells-done progress).

Timestamps are microseconds since the Unix epoch (Chrome only requires
a consistent µs clock), so spans from separate processes or resumed
jobs land on one coherent timeline.

:data:`NULL_TRACER` is the no-op sink: instrumented code paths take a
``tracer`` and default to it, so tracing off costs one attribute call
per event site and nothing else.  Any object with the same methods
(``enabled``, ``now_us``, ``complete``, ``instant``, ``counter``) is a
tracer.

The program's spans go through :func:`span`, which also opens a
``jax.profiler.TraceAnnotation`` of the span's name: under an active
profiler every span appears on the profile's host plane, on the clock
of the device ops.  :func:`tagged` adds fixed args to every event of a
tracer, so that the spans of one replan share its ``replan`` ordinal
and those of one campaign cell its ``slug``.

Span vocabulary (children nest inside their parent's interval):

=======================  ===================  =================================
span                     parent               args
=======================  ===================  =================================
``job_open``             —                    ``job``: spec fingerprint, plan
                                              cache, manifest (recorded once
                                              the tracer exists, with its
                                              measured start)
``prep_topo``            —                    ``slug``, ``cached`` (every plan
                                              from the plan cache or memory)
``cell``                 —                    ``slug``, ``topo``, ``pattern``,
                                              ``algo``, ``scenario``, ``lanes``
``chunk``                ``cell``             ``cycles``, ``compiled`` (a new
                                              runner was built for the length)
``cell_save``            —                    ``slug``: npz, sidecar, telemetry
``epoch``                ``cell`` or —        ``t0``, ``t1``, ``cycles``,
                                              ``compiled``, ``scenario``,
                                              ``policy``
``boundary``             ``cell`` or —        ``cycle``, ``host_bytes``,
                                              ``nonzero_pairs``: counters read,
                                              estimator, detector, due events,
                                              control decision
``replan``               ``cell`` or —        ``replan``, ``cycle``,
                                              ``trigger``, ``warm``,
                                              ``iterations``, ``unroutable``,
                                              ``drift_tv``; left out when the
                                              hot-swap guard rejects the plan
``build_plan_fast``      ``replan`` or —      ``nodes``, ``warm``, ``faults``
``plan_statics``         ``build_plan_fast``  ``faults``, ``ndim``, ``orders``
``plan_device``          ``build_plan_fast``  ``warm``: inputs to the device,
                                              the jitted plan, results back
``plan_assemble``        ``build_plan_fast``  —
``certify``              a gate, ``replan``   ``label``, ``verdict``, CDG
                                              sizes, ``wall_ms``,
                                              ``wrap_dims`` (dimensions
                                              with distinct wrap links)
``greedy_refine``        ``replan``           ``pairs`` (with traffic),
                                              ``visited`` (those that could
                                              flip, swept), ``sweeps_run``,
                                              ``changed``,
                                              ``route_cache_hit`` (route
                                              links of the fabric reused),
                                              ``orders``, ``hops`` (route
                                              hops of every order over the
                                              visited pairs)
``hot_swap``             ``replan``           ``cycle``, ``shed_pairs``,
                                              ``rejected``: shed guard,
                                              admission control, table retarget
``build_plans_batched``  —                    ``nodes``, ``lanes``, ``faults``
=======================  ===================  =================================

Instants: ``drift_detected``, ``LinkFail`` / ``LinkRecover`` /
``TrafficDrift``, ``hot_swap_rejected``, ``plan_cache_hit`` /
``plan_cache_miss``, ``watchdog_tripped`` and the event log's kinds.
Counter: ``drift_tv``.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

__all__ = ["TraceWriter", "NullTracer", "NULL_TRACER", "SpanArgs", "span",
           "tagged", "clock_us", "read_trace", "validate_events"]


def clock_us() -> float:
    """The trace clock: microseconds since the Unix epoch."""
    return time.time() * 1e6


class NullTracer:
    """No-op tracer with the :class:`TraceWriter` emit interface."""

    enabled = False

    def now_us(self) -> float:
        return 0.0

    def instant(self, name, **kw) -> None:
        pass

    def counter(self, name, values, **kw) -> None:
        pass

    def complete(self, name, ts_us, dur_us, **kw) -> None:
        pass

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass


NULL_TRACER = NullTracer()


class SpanArgs(dict):
    """The args of an open :func:`span`: the body adds the counts it
    finds, and :meth:`drop` leaves the span out of the tracer."""

    dropped = False

    def drop(self) -> None:
        self.dropped = True


@contextlib.contextmanager
def span(tracer, name: str, *, cat: str = "host", **args):
    """``with span(tracer, "replan", cycle=t) as a:`` — one complete
    event of the body's host wall time, and a profiler annotation of the
    same name around it.

    Yields a :class:`SpanArgs` holding ``args``; what the body adds to it
    reaches ``tracer.complete``, which is the only tracer method called
    (exceptions are flagged ``error`` and re-raised).  The span blocks on
    nothing: device time is the profile's to tell."""
    from jax.profiler import TraceAnnotation   # the report renders without JAX

    a = SpanArgs(args)
    ann = TraceAnnotation(name)
    ann.__enter__()
    t0 = tracer.now_us()
    try:
        yield a
    except BaseException:
        a["error"] = True
        raise
    finally:
        dur = tracer.now_us() - t0
        ann.__exit__(None, None, None)
        if tracer.enabled and not a.dropped:
            tracer.complete(name, t0, dur, cat=cat, args=dict(a) or None)


class _Tagged:
    """A tracer that adds fixed args to the spans and instants of
    another (see :func:`tagged`)."""

    enabled = True

    def __init__(self, inner, args: dict):
        self._inner, self._args = inner, args

    def now_us(self) -> float:
        return self._inner.now_us()

    def complete(self, name, ts_us, dur_us, *, args=None, **kw) -> None:
        self._inner.complete(name, ts_us, dur_us,
                             args={**self._args, **(args or {})}, **kw)

    def instant(self, name, *, args=None, **kw) -> None:
        self._inner.instant(name, args={**self._args, **(args or {})},
                            **kw)

    def counter(self, name, values, **kw) -> None:
        self._inner.counter(name, values, **kw)

    def flush(self) -> None:
        self._inner.flush()


def tagged(tracer, **args):
    """``tracer`` with ``args`` added to each of its spans and instants
    (a span's own args win); :data:`NULL_TRACER` for an absent or
    disabled tracer."""
    if tracer is None or not tracer.enabled:
        return NULL_TRACER
    return _Tagged(tracer, args)


class TraceWriter:
    """Streaming Chrome trace-event writer (see module docstring).

    ``append=True`` (the default) continues an existing stream — the
    resume path: the array stays unterminated, so the concatenation of
    a job's runs is one valid trace.  Thread-safe: the campaign
    service emits from a daemon thread while ``status()`` pollers run
    on the caller's.
    """

    enabled = True

    def __init__(self, path: str, *, pid: str = "qstar",
                 append: bool = True):
        self.path = str(path)
        self.pid = str(pid)
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)
        self._lock = threading.Lock()
        self._f = open(self.path, "a" if append else "w")
        if self._f.tell() == 0:
            self._f.write("[\n")
            self._f.flush()

    def now_us(self) -> float:
        """Current timestamp on the trace clock (Unix epoch µs)."""
        return clock_us()

    # ------------------------------------------------------------- #
    def _emit(self, ev: dict) -> None:
        line = json.dumps(ev, sort_keys=True, default=str)
        with self._lock:
            self._f.write(line + ",\n")
            self._f.flush()

    def instant(self, name: str, *, cat: str = "ctrl",
                args: dict | None = None, tid: int = 0,
                ts_us: float | None = None) -> None:
        ev = {"name": name, "ph": "i", "cat": cat, "s": "t",
              "ts": self.now_us() if ts_us is None else ts_us,
              "pid": self.pid, "tid": tid}
        if args:
            ev["args"] = args
        self._emit(ev)

    def counter(self, name: str, values: dict, *, cat: str = "ctrl",
                tid: int = 0, ts_us: float | None = None) -> None:
        self._emit({"name": name, "ph": "C", "cat": cat,
                    "ts": self.now_us() if ts_us is None else ts_us,
                    "pid": self.pid, "tid": tid,
                    "args": {k: float(v) for k, v in values.items()}})

    def complete(self, name: str, ts_us: float, dur_us: float, *,
                 cat: str = "host", args: dict | None = None,
                 tid: int = 0) -> None:
        ev = {"name": name, "ph": "X", "cat": cat, "ts": ts_us,
              "dur": max(float(dur_us), 0.0), "pid": self.pid,
              "tid": tid}
        if args:
            ev["args"] = args
        self._emit(ev)

    def flush(self) -> None:
        with self._lock:
            self._f.flush()

    def close(self) -> None:
        """Close the file handle.  The array is deliberately left
        unterminated — valid per the trace-event spec, and the only
        representation that survives a kill at any byte."""
        with self._lock:
            if not self._f.closed:
                self._f.flush()
                self._f.close()


# ------------------------------------------------------------------- #
# readers (reports + tests)
# ------------------------------------------------------------------- #
def read_trace(path: str) -> list[dict]:
    """Parse a (possibly unterminated) JSON-array trace stream.

    Tolerates the trailing comma and missing ``]`` of a killed stream —
    the same leniency Perfetto's importer applies."""
    with open(path) as f:
        text = f.read()
    body = text.strip()
    if body.startswith("["):
        body = body[1:]
    body = body.rstrip().rstrip("]").rstrip().rstrip(",")
    if not body:
        return []
    return json.loads("[" + body + "]")


_PHASES = {"X", "i", "C"}


def validate_events(events: list[dict]) -> list[str]:
    """Schema check of the vocabulary this package emits; returns a
    list of problems (empty == valid)."""
    problems = []
    for i, ev in enumerate(events):
        for field in ("name", "ph", "ts", "pid"):
            if field not in ev:
                problems.append(f"event {i}: missing {field!r}")
        ph = ev.get("ph")
        if ph not in _PHASES:
            problems.append(f"event {i}: unknown phase {ph!r}")
        if ph == "X" and "dur" not in ev:
            problems.append(f"event {i}: complete event without dur")
        if ph == "C" and not isinstance(ev.get("args"), dict):
            problems.append(f"event {i}: counter without args dict")
        ts = ev.get("ts")
        if ts is not None and not isinstance(ts, (int, float)):
            problems.append(f"event {i}: non-numeric ts {ts!r}")
    return problems
