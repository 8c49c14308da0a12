"""Unified observability layer (PR 9).

Four pieces, one import surface:

* :class:`MetricsRegistry` / :class:`RegistryBackedStats` — the single
  counter/gauge/histogram store behind every subsystem's stats object;
* :func:`span` / :class:`Tracer` — nested spans: non-blocking profiler
  annotations while ``jax.profiler`` collects, device-sync close and
  Chrome-trace export (Perfetto) under a Tracer, a shared no-op otherwise;
* :func:`watchdog` / :class:`CompileWatchdog` — runtime guard promoting
  the "compiles == buckets" test idiom (strict + seal modes);
* :func:`write_slo` — Prometheus text + JSON snapshot of the serving
  SLO metrics.

See docs/OBSERVABILITY.md for the span taxonomy and metric catalog.
"""

from .registry import MetricsRegistry, RegistryBackedStats
from .memory import (
    ALLOC_CHECK_MODULES, KNOWN_ALLOC_SITES, MEMORY_FAMILIES,
    DeviceMemoryAccountant, account, accountant, estimate_footprint, pin,
    set_accounting, will_fit,
)
from .trace import Span, Tracer, gc_spans, get_tracer, set_tracer, span
from .watchdog import (
    KERNEL_FAMILIES, KNOWN_JIT_SITES, CompileRecord, CompileWatchdog,
    WatchdogError, watchdog,
)
from .export import slo_snapshot, to_prometheus, write_slo

__all__ = [
    "MetricsRegistry", "RegistryBackedStats",
    "Span", "Tracer", "gc_spans", "get_tracer", "set_tracer", "span",
    "CompileRecord", "CompileWatchdog", "WatchdogError", "watchdog",
    "KERNEL_FAMILIES", "KNOWN_JIT_SITES",
    "DeviceMemoryAccountant", "accountant", "set_accounting",
    "account", "pin", "estimate_footprint", "will_fit",
    "MEMORY_FAMILIES", "KNOWN_ALLOC_SITES", "ALLOC_CHECK_MODULES",
    "slo_snapshot", "to_prometheus", "write_slo",
]
