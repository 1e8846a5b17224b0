"""repro.obs — spans, device scopes, metrics and trace export.

One observability layer across the engine (``core.hytm``), mesh
(``dist.graph_shard``), streaming (``stream.service``), and serving
(``serve.scheduler`` / ``serve.warm_cache``) stacks:

* :func:`span` — a live host span: a ``jax.profiler.TraceAnnotation``
  on the profiler's clock, and, given a :class:`TraceRecorder`, a span
  in its ring too (``trace.py``); :func:`set_stats` adds the stats its
  body learns while it is open;
* ``scopes`` — the ``jax.named_scope`` names of the engine's layers,
  and ``op_scopes()``, the map from each compiled instruction to its
  scope that a device-trace reader needs (``scopes.py``);
* :class:`TraceRecorder` — span/instant/counter ring with virtual-clock
  *and* wall-clock timestamps;
* :class:`MetricsRegistry` — labeled counter/gauge/histogram registry
  for picks, modelled bytes and seconds, mispredictions, admission,
  cache tiers and lane occupancy (``metrics.py``);
* ``export`` — Chrome trace-event JSON (``chrome://tracing`` /
  Perfetto) and a ``summary()``/``reconcile()`` that cross-checks the
  recorder against ``HyTMResult`` totals exactly.

Contract:

* scopes are metadata only: they set the ``op_name`` of the ops traced
  inside them and never change a compiled op, so they are always on;
* spans are always emitted, and inert without a running profile or a
  recorder (about a microsecond each);
* instants, counters and metrics come from drained chunk history and
  scheduler/cache callbacks, never from inside jit-traced code, so
  ``obs=None`` and a recorder run bit-identical programs.

Counters named ``modeled_*`` come from the cost model (PCIe-3 by
default), not from the device; measured times come only from a device
trace.  Gated by ``benchmarks/obs_bench.py --selfcheck``.
"""

from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import TraceEvent, TraceRecorder, set_stats, span
from repro.obs.export import (
    reconcile,
    summary,
    to_chrome_trace,
    validate_chrome_trace,
    write_chrome_trace,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "TraceEvent",
    "TraceRecorder",
    "reconcile",
    "set_stats",
    "span",
    "summary",
    "to_chrome_trace",
    "validate_chrome_trace",
    "write_chrome_trace",
]
