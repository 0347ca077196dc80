"""Observability plane — zero-dependency tracing + metrics.

Two stdlib-only modules the whole pipeline threads through:

- :mod:`.trace` — monotonic-clock span API (context-var propagated,
  request-id correlated) exporting Chrome/Perfetto trace-event JSON.
  Disabled mode is a single flag check per call site; the serving hot
  path carries per-dispatch spans only (never per-op — the
  ``per-op-host-loop`` discipline applies to instrumentation too).
  Its profiler sink (``trace.enable(annotate=...)``, which puts spans
  on the clock of the device trace) is injected by the caller, never
  imported here.
- :mod:`.metrics` — counters/gauges/fixed-bucket histograms whose
  p50/p95/p99 are derivable without storing samples, rendered as
  Prometheus text and a JSON snapshot (the service ``kind:"metrics"``
  scrape).

This package must stay import-light (stdlib only, no jax/numpy, not
even ``jax.profiler``): the dispatch modules it instruments import it
at module top, and the analysis rule ``raw-clock-in-pipeline`` makes
:func:`trace.monotonic` the one sanctioned clock there. See
``docs/observability.md``.
"""

from . import metrics, trace

__all__ = ["metrics", "trace"]
