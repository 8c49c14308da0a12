"""One run of one benchmark cell, driven by the names in ``BENCHMARK.json``.

A cell names a configuration (``configs/<config>.json``) and a traffic mix
(``traffic/<mix>.json``); its per-layer metrics are readers
``layers/<metric>.py``.  Nothing here names a cell, a mix or a metric, so a
later change adds a cell by adding files and entries.

A run: make the configuration's graph, set up and warm the cell's shapes
(counted as ``setup_s``, from process start to window start), measure the
window (``--trace 0``: end-to-end metrics; ``--trace 1``: a fixed amount of
work under the JAX profiler, reduced to per-layer metrics), read the device's
peak memory, free the program's state, then compare the answers with the
plain reference.
"""

from __future__ import annotations

import contextlib
import functools
import importlib.util
import inspect
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

from . import drive, trace
from .data import graphs
from .reference import Check

__all__ = ["BENCH", "CompileClock", "LayerContext", "NoDevice", "instrument",
           "load_layer", "peaks_for", "run_cell", "spec"]

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = BENCH / ".jax_cache"

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoDevice(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def _for_cell(metric: dict, cell: str, e2e_of_cell: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e_of_cell


def peaks_for(kind: str) -> dict:
    """The peaks of a ``device_kind``; a device not in the table is an error."""
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


class CompileClock:
    """Times of JAX's backend compiles (an executable built, whether compiled
    or loaded from the persistent cache), from ``jax.monitoring``."""

    def __init__(self):
        self.times: List[float] = []

    def _on(self, name: str, secs: float, **_kw) -> None:
        if name == BACKEND_COMPILE:
            self.times.append(time.perf_counter())

    @property
    def count(self) -> int:
        return len(self.times)

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


# Calls into the program's layers that a traced run marks in the profiler's
# timeline (the idle gaps are named by them), as (module, owner, attribute).
# Each must exist: a name the program no longer has fails the traced run,
# rather than leave a layer's gaps unnamed or the roofline with no sweeps.
_MARKED = (
    ("repro.core.engine", "LPEngine", ("cluster", "refine", "contract",
     "project", "evolve_device", "cut", "block_weights", "to_host",
     "to_arena", "_pack")),
    ("repro.core.multilevel", None, ("repair_balance", "cut_np", "evolve",
     "imbalance_np", "_detect_type", "_uncoarsen")),
)
# LP sweeps whose real size the roofline reader needs: the level's graph
# ``g`` and the ``iters`` the call runs (``_lp_sweep`` is a fixed-count loop)
_SWEEPS = {"cluster", "refine"}
_SWEEP_ARGS = ("g", "iters")


class Unmarked(RuntimeError):
    """A name the traced run marks is missing from the program."""


def _sweep_size(sig, a, kw) -> dict:
    b = sig.bind(*a, **kw).arguments
    g, iters = b["g"], b["iters"]
    return dict(n=int(g.n), m=int(g.m), iters=int(iters))


@contextlib.contextmanager
def instrument(sweeps: list):
    """While open, wrap each call of ``_MARKED`` in a profiler annotation
    ``bench:<name>`` (no device sync), and append the ``n``, ``m`` and
    ``iters`` of every engine LP sweep to ``sweeps``.  Raises
    :class:`Unmarked` when a marked name or a sweep argument is missing."""
    import jax

    undo = []
    try:
        for modname, owner_name, attrs in _MARKED:
            mod = importlib.import_module(modname)
            owner = getattr(mod, owner_name) if owner_name else mod
            for attr in attrs:
                orig = owner.__dict__.get(attr) if owner_name else getattr(owner, attr, None)
                if orig is None:
                    where = ".".join(x for x in (modname, owner_name, attr) if x)
                    raise Unmarked(f"{where} is gone: bench/harness.py marks it")
                label = f"bench:{owner_name or modname.rsplit('.', 1)[1]}.{attr}"
                sig = None
                if attr in _SWEEPS:
                    sig = inspect.signature(orig)
                    missing = [x for x in _SWEEP_ARGS if x not in sig.parameters]
                    if missing:
                        raise Unmarked(f"{label} takes no {missing}: the sweep "
                                       "roofline reads them")

                def wrapped(*a, __orig=orig, __label=label, __sig=sig, **kw):
                    if __sig is not None:
                        sweeps.append(_sweep_size(__sig, a, kw))
                    with jax.profiler.TraceAnnotation(__label):
                        return __orig(*a, **kw)

                functools.update_wrapper(wrapped, orig)
                setattr(owner, attr, wrapped)
                undo.append((owner, attr, orig))
        yield
    finally:
        for owner, attr, orig in reversed(undo):
            setattr(owner, attr, orig)


@dataclass
class LayerContext:
    """What a per-layer reader may read."""
    reduced: Optional[trace.Reduced]
    units: int                      # partitions in the window
    compiles: int                   # executables built inside the window
    sweeps: List[dict] = field(default_factory=list)
    peaks: Dict[str, float] = field(default_factory=dict)


def load_layer(name: str):
    path = BENCH / "layers" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench.layers.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak(chips: int) -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def _traced(load, rounds: int, sweeps: list):
    """The window under the profiler, and its reduction."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # Python-call tracing slows the host
    opts.enable_hlo_proto = False
    out = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            with instrument(sweeps), jax.profiler.TraceAnnotation(trace.WINDOW):
                w = load.run(rounds=rounds)
        finally:
            jax.profiler.stop_trace()
        files = sorted(Path(out).rglob("*.xplane.pb"))
        reduced = trace.reduce_file(str(files[-1])) if files else None
    finally:
        shutil.rmtree(out, ignore_errors=True)
    return w, reduced


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             t_start: float, root: Path = ROOT, prog=None,
             require_tpu: bool = True, config: Optional[dict] = None,
             traffic: Optional[dict] = None) -> dict:
    """One run of one cell; returns the result line's object.  ``prog``,
    ``config`` and ``traffic`` replace the program, the configuration and
    the mix (the tests plant faults and shrink sizes through them);
    ``require_tpu`` False skips the look for a chip."""
    bench = spec(root)
    cell = _named(bench["workloads"], workload, "workload")
    cfg_entry = _named(bench["configs"], cell["config"], "config")
    if config is None:
        config = json.loads((root / cfg_entry["file"]).read_text())
    if traffic is None:
        traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    chips = int(cell["chips"])

    info = device_info()
    if require_tpu and (info["platform"] != "tpu" or info["count"] < chips):
        raise NoDevice(f"cell {workload} needs {chips} TPU chip(s); JAX found "
                       f"{info['count']} {info['platform']} device(s)")
    peaks = peaks_for(info["kind"]) if traced and require_tpu else {}

    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    e2e_names = {m["name"] for m in e2e}
    layers = [m for m in bench["per_layer"] if _for_cell(m, workload, e2e_names)]

    prog = prog or drive.program()
    g = graphs.build(config, int(config["graph_seed"]))
    load = drive.Offline(prog, g, config, traffic, seed)
    with CompileClock() as clock:
        load.setup()
        sweeps: list = []
        if traced:
            w, reduced = _traced(load, int(traffic["traced_rounds"]), sweeps)
        else:
            w, reduced = load.run(seconds=seconds), None
        compiles = clock.between(w.t0, w.t1)
    setup_s = w.t0 - t_start

    device = {"platform": info["platform"], "kind": info["kind"],
              "count": chips, "memory_peak_bytes": memory_peak(chips)}
    values: Dict[str, float] = {}
    breakdown = None
    if traced:
        if reduced is not None:
            device["busy_s"] = reduced.busy_s
            device["window_s"] = reduced.window_s
            breakdown = {"device_ops": trace.top(reduced.op_s),
                         "idle_gaps": trace.top(reduced.idle)}
        ctx = LayerContext(reduced=reduced, units=len(w.answers),
                           compiles=compiles, sweeps=sweeps, peaks=peaks)
        for m in layers:
            v = load_layer(m["name"]).read(ctx)
            if v is not None:
                values[m["name"]] = v
        wanted = layers
    else:
        values.update(load.end_to_end(w))
        values["setup_s"] = setup_s
        wanted = e2e
    print(f"bench: {workload} seed={seed} setup_s={setup_s:.3f} "
          f"window_s={w.elapsed:.3f} {load.units}={len(w.answers)} "
          f"window_compiles={compiles}", file=sys.stderr)

    load.free(w)
    checks: List[Check] = load.checks(w) if w.answers else []
    correct = bool(w.answers) and w.failed == 0 and all(c.ok for c in checks)

    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if m["name"] in values}
    out = {"correct": correct, "attempted": w.attempted, "failed": w.failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value if math.isfinite(c.value) else None,
                              "limit": c.limit} for c in checks}
    return out


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    keeping every executable however fast it compiled."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def environment_ok(root: Path = ROOT) -> Optional[str]:
    """None when the checkout holds the program; else what is missing."""
    if not (root / "src" / "repro").is_dir():
        return f"no program under {root / 'src' / 'repro'}"
    return None

