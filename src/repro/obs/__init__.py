"""repro.obs — the flight recorder.

Three planes of observability over the NoC stack:

* :mod:`repro.obs.probe` — in-sim telemetry ring buffers collected
  inside the jitted chunk scan (off by default, bit-identical when
  off);
* :mod:`repro.obs.trace` — Chrome trace-event streaming for ctrl-plane
  events and host-side spans (Perfetto-viewable; each span is also a
  profiler annotation, on the device trace's clock), plus
  :mod:`repro.obs.log`'s structured event log behind the ``verbose=``
  flags;
* :mod:`repro.obs.report` — per-job report rendering (trajectories,
  replan timeline) from a campaign job's persisted telemetry, trace,
  and metrics streams.
"""

from .log import EventLog, NULL_LOG
from .probe import (TEL_COUNT_FIELDS, TEL_KEYS, Telemetry,
                    resolved_epoch, telemetry_state)
from .trace import (NULL_TRACER, NullTracer, TraceWriter, read_trace,
                    validate_events)

__all__ = [
    "EventLog", "NULL_LOG",
    "TEL_COUNT_FIELDS", "TEL_KEYS", "Telemetry", "resolved_epoch",
    "telemetry_state",
    "NULL_TRACER", "NullTracer", "TraceWriter", "read_trace",
    "validate_events",
]
