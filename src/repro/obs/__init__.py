"""Tracing & telemetry for the serving stack (docs/observability.md).

NeCTAr's evaluation attributes time and traffic to the right unit —
near-core vs near-memory accelerator, weight bytes vs KV bytes. This
package is the same discipline at the software level: every engine tick
is decomposed into phase spans with host/device attribution, every
request carries a lifecycle timeline, and every subsystem's counters
live in one registry that all exporters read.

  trace     Tracer / NULL_TRACER: per-tick phase spans (schedule ->
            draft -> batch_assemble -> device_dispatch -> device_wait ->
            sample_sync -> postprocess), request lifecycle events,
            per-tick host/device/padding aggregates. Disabled mode is a
            shared no-op singleton — near-zero overhead, asserted in
            tier-1.
  compiles  the one process-wide jax.monitoring listener: compiles
            during an engine tick count in engine_compiles_total by
            function name, and in the tick's ``compiles`` stat.
  registry  Counter/Gauge/Histogram registry: the shared substrate
            engine, scheduler, pool, prefix cache, and spec metrics
            register into; metrics.summary() and every exporter read
            from it.
  export    Perfetto/Chrome-trace JSON (one lane per engine phase, one
            per request), JSONL structured log, Prometheus text +
            scrape endpoint (launch.serve --metrics-port/--trace-out).
  costmodel Static cost of the compiled serving step per width bucket:
            cost_analysis() totals + per-jax.named_scope FLOP/byte
            attribution parsed from the optimized HLO (unrolled twin).
  profile   Roofline attainment (ObsConfig.profile / launch.serve
            --profile): static cost joined with measured device_wait
            time -> per-bucket achieved GFLOP/s, GB/s, arithmetic
            intensity, and % of the active hardware spec's roofline.

Turn on with ``ServeConfig(obs=ObsConfig(enabled=True))``; greedy
output is token-identical tracing on or off (tracing observes, never
schedules).
"""

from repro.obs.export import (perfetto_trace, start_metrics_server,
                              write_jsonl, write_perfetto)
from repro.obs.profile import ServingProfiler, attainment_table
from repro.obs.registry import Counter, Gauge, Histogram, Registry
from repro.obs.trace import (NULL_TRACER, Event, NullTracer, Span, Tracer,
                             make_tracer)

__all__ = [
    "Counter", "Event", "Gauge", "Histogram", "NULL_TRACER", "NullTracer",
    "Registry", "ServingProfiler", "Span", "Tracer", "attainment_table",
    "make_tracer", "perfetto_trace", "start_metrics_server",
    "write_jsonl", "write_perfetto",
]
