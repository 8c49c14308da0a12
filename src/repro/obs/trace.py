"""Program spans: on the profiler's timeline, or in a Chrome-trace Tracer.

``span()`` is the one instrumentation entry point; what it returns depends
on what is collecting:

* nothing (no :class:`Tracer` installed, the JAX profiler off): the shared
  no-op singleton — one global load, one ``None`` test and one
  ``TraceAnnotation.is_enabled()`` call on the hot path, no allocation;
* the JAX profiler (``jax.profiler.start_trace`` / ``trace``): a
  non-blocking ``jax.profiler.TraceAnnotation`` named after the span, so
  the span lands on the same clock as the device planes.  Its args (and
  ``set(...)`` later) arrive as stats on the host event, with ``cat``
  marking it as a program span.  Device time comes from the device
  planes, so ``sync_on`` does nothing here;
* a :class:`Tracer`: nested wall-clock intervals with an explicit
  device-sync boundary — a span that wraps device work registers its
  output arrays via ``sp.sync_on(...)`` and the close calls
  ``jax.block_until_ready`` (the Tracer's spans measure device
  completion).  With the profiler on as well, the annotation is emitted
  too.

Usage::

    from repro.obs import span, set_tracer, Tracer

    set_tracer(Tracer())            # enable (None disables again)
    with span("repair.sweep", cat="repair", region=int(nr)) as sp:
        out = _lp_sweep(...)
        sp.sync_on(out)             # close blocks until device-done
    get_tracer().export_chrome("trace.json")   # load in ui.perfetto.dev

Span taxonomy (docs/OBSERVABILITY.md has the catalog): ``partition``,
``partition.finalize``, ``vcycle.*`` (level/pack/sweep/contract/evolve/
uncoarsen/project), ``host.read``, ``py.gc``, ``repair.*`` (expand/
gather/sweep/gain/balance), ``store.*`` (compact/view/vacuum),
``group.lane``, ``deploy.migrate``, ``resilience.audit``,
``resilience.snapshot``, ``wal.fsync``, ``checkpoint.write``,
``session.update``.
"""

from __future__ import annotations

import gc
import json
import os
import threading
import time
from typing import List, Optional

from jax.profiler import TraceAnnotation as _Annotation

from .memory import accountant as _mem_accountant

__all__ = ["Tracer", "Span", "span", "gc_spans", "get_tracer", "set_tracer"]

# whether a profiler session is collecting: the static TraceMe check
_profiling = _Annotation.is_enabled


def _annotation(name: str, cat: str, args: dict) -> _Annotation:
    """The profiler event of a span; ``cat`` marks it as a program span."""
    return _Annotation(name, cat=cat or name.split(".")[0], **args)


class _NoopSpan:
    """The disabled path: every method is a no-op, one shared instance."""

    __slots__ = ()
    active = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def sync_on(self, *arrays):
        pass

    def set(self, **args):
        pass


_NOOP = _NoopSpan()


class _ProfilerSpan:
    """A span while only the JAX profiler collects: a non-blocking
    annotation on the profiler's clock (device time comes from the device
    planes, so ``sync_on`` never blocks)."""

    __slots__ = ("_ann",)
    active = True

    def __init__(self, name: str, cat: str, args: dict):
        self._ann = _annotation(name, cat, args)

    def __enter__(self):
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        return False

    def sync_on(self, *arrays):
        pass

    def set(self, **args):
        self._ann.set_metadata(**args)


class Span:
    __slots__ = ("tracer", "name", "cat", "args", "_sync", "_ann", "t0", "tid")
    active = True

    def __init__(self, tracer: "Tracer", name: str, cat: str, args: dict):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._sync = None
        self._ann = None
        self.t0 = 0.0
        self.tid = 0

    def __enter__(self):
        if _profiling():
            self._ann = _annotation(self.name, self.cat, self.args)
            self._ann.__enter__()
        self.tid = threading.get_ident() & 0xFFFF
        self.t0 = time.perf_counter()
        return self

    def sync_on(self, *arrays):
        """Arrays whose device completion bounds this span (closed-over by
        ``__exit__``; the block happens only because a Tracer is on)."""
        self._sync = arrays

    def set(self, **args):
        self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc):
        if self._sync is not None:
            import jax

            try:
                jax.block_until_ready(self._sync)
            except Exception:
                pass   # tracing must never turn a serving error into another
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.tracer._record(self, t1)
        return False


class _GcSpans:
    """Hooks ``gc.callbacks`` while open: each collection becomes a
    ``py.gc`` annotation (args ``generation`` and ``collected``)."""

    __slots__ = ("_open",)

    def __init__(self):
        self._open = {}                 # thread id -> open annotation

    def __enter__(self):
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        return False

    def _on_gc(self, phase: str, info: dict) -> None:
        tid = threading.get_ident()
        if phase == "start":
            ann = _Annotation("py.gc", cat="python",
                              generation=int(info["generation"]))
            ann.__enter__()
            self._open[tid] = ann
        else:
            ann = self._open.pop(tid, None)
            if ann is not None:
                ann.set_metadata(collected=int(info["collected"]))
                ann.__exit__(None, None, None)


def gc_spans():
    """Context in which every Python collection is a ``py.gc`` span, when
    the profiler is collecting as it opens; the shared no-op otherwise, so
    the hook costs nothing with tracing off."""
    return _GcSpans() if _profiling() else _NOOP


class Tracer:
    """Collects complete ("ph": "X") Chrome trace events, microsecond ts."""

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.events: List[dict] = []
        self._origin = time.perf_counter()
        self._lock = threading.Lock()

    def span(self, name: str, cat: str = "", **args):
        if not self.enabled:
            return _NOOP
        return Span(self, name, cat, args)

    def _record(self, sp: Span, t1: float) -> None:
        ev = dict(
            name=sp.name, cat=sp.cat or sp.name.split(".")[0], ph="X",
            ts=(sp.t0 - self._origin) * 1e6, dur=(t1 - sp.t0) * 1e6,
            pid=os.getpid(), tid=sp.tid,
        )
        if sp.args:
            ev["args"] = sp.args
        # memory accounting hooks: every span close is a watermark boundary
        # (per V-cycle level, per repair phase) and a Perfetto counter-track
        # sample ("ph": "C") in the same trace
        acct = _mem_accountant()
        mem_ev = None
        if acct.enabled:
            acct.note_span(sp.name, sp.args)
            mem_ev = acct.counter_event(
                ts=(t1 - self._origin) * 1e6, pid=ev["pid"]
            )
        with self._lock:
            self.events.append(ev)
            if mem_ev is not None:
                self.events.append(mem_ev)

    def clear(self) -> None:
        with self._lock:
            self.events.clear()

    def export_chrome(self, path: str) -> str:
        """Write ``{"traceEvents": [...]}`` — drag into ui.perfetto.dev."""
        with self._lock:
            doc = dict(
                traceEvents=list(self.events),
                displayTimeUnit="ms",
            )
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


_tracer: Optional[Tracer] = None


def get_tracer() -> Optional[Tracer]:
    return _tracer


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install (or with ``None`` remove) the process-global tracer."""
    global _tracer
    prev, _tracer = _tracer, tracer
    return prev


def span(name: str, cat: str = "", **args):
    """The instrumentation entry point every subsystem calls.

    Disabled fast path: one global load, one ``None`` test, one
    ``is_enabled()`` call, return the shared no-op singleton — no
    allocation, no branching at close.  Under the profiler alone the span
    is a non-blocking annotation; under a :class:`Tracer` it records (and
    also annotates when the profiler is on).
    """
    t = _tracer
    if t is None or not t.enabled:
        if _profiling():
            return _ProfilerSpan(name, cat, args)
        return _NOOP
    return Span(t, name, cat, args)
