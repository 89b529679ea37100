r"""jaxmc.obs — run telemetry (phase spans, counters, per-level BFS
metrics) with JSONL trace streaming, a JSON summary artifact, a
watchdog heartbeat/stall monitor, distributed trace context, a
search-progress/ETA estimator, and a cross-run report CLI.

    from jaxmc import obs

    tel = obs.Telemetry(trace_path="run.jsonl", meta={"backend": "jax"})
    wd = obs.Watchdog(tel).start()           # heartbeat + stall events
    with obs.use(tel):                       # engines see it via current()
        with tel.span("load"):
            ...
    wd.stop()
    tel.write_metrics("m.json", result={...})

Engines report through `obs.current()` — a no-op NullTelemetry unless a
real recorder is installed — so instrumentation costs nothing when no
artifact was requested. See obs/telemetry.py for the model,
obs/schema.py for the artifact schema (jaxmc.metrics/4),
obs/context.py for the JAXMC_TRACE_CTX propagation contract,
obs/progress.py for the ETA estimator, obs/watchdog.py for live stall
diagnosis, obs/prof.py for the per-dispatch device profiler,
obs/ledger.py for the persistent run ledger, and obs/report.py
for `python -m jaxmc.obs report|diff|timeline|top|history` over
artifacts.
"""

from . import context
from .telemetry import (Logger, NullTelemetry, Telemetry, current,
                        device_mem_high_water, environment_meta,
                        live_devices, prom_name, stamp_device, rss_bytes, use, use_local,
                        write_json_atomic)
from .context import TraceContext, child_env
from .ledger import append_summary, ledger_path
from .prof import Profiler, prof_attribution, prof_wrap
from .progress import ProgressEstimator, attach_estimator, eta_suffix
from .schema import (CHECK_KEYS, HEARTBEAT_KEYS, REQUIRED_KEYS,
                     RESULT_KEYS, SCHEMA, SCHEMAS, STALL_KEYS,
                     validate_summary, validate_trace_event)
from .watchdog import Watchdog

__all__ = ["Logger", "NullTelemetry", "Profiler", "Telemetry",
           "Watchdog", "TraceContext", "ProgressEstimator",
           "append_summary", "attach_estimator", "child_env", "context",
           "current", "device_mem_high_water", "environment_meta",
           "eta_suffix", "ledger_path", "live_devices",
           "prof_attribution", "prof_wrap", "prom_name", "rss_bytes",
           "stamp_device", "use", "use_local", "write_json_atomic",
           "SCHEMA", "SCHEMAS",
           "REQUIRED_KEYS", "CHECK_KEYS", "RESULT_KEYS",
           "HEARTBEAT_KEYS", "STALL_KEYS", "validate_summary",
           "validate_trace_event"]
