#!/usr/bin/env python3
"""The program's own spans in a profiler trace, and a probe that reads them.

``repro.obs.span`` emits a ``jax.profiler.TraceAnnotation`` for every span
while the profiler collects; each carries a ``cat`` stat, which marks it as
a program span, and its args as stats (the ``partition`` span closes with
the engine's ``host_reads``, ``evo_grow_rounds`` and ``evo_grow_budget``).
This module reduces those annotations:

* :attr:`SpanReduction.spans`: per span name, the count, total seconds,
  self seconds inside the window and summed numeric args;
* :attr:`SpanReduction.idle_spans`: the window's device-idle seconds keyed
  by the innermost program span covering each gap ("host" where none does);
* :func:`host_pack_s`, :func:`host_reads` and :func:`evo_grow_rounds`: the
  per-partition numbers the benchmark's readers are to report.

Run as a script on a machine with a chip, it times windows of whole
``partition()`` rounds of a benchmark configuration without the profiler,
under it with the program's spans off, and under it with them on, and
prints one JSON line per round (window seconds in each mode, the traced
round's per-call ``partition`` seconds, span self seconds, ``host.read``
seconds by what was read, idle by span, the share of idle seconds inside a
span below ``partition``)::

    python3 bench/spans.py --config rgg-mesh --rounds 3 --seed 7
    python3 bench/spans.py --config rmat-web --rounds 10 --seed 7 --modes traced
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
import warnings
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

if __package__ in (None, ""):     # run as a script: the checkout's packages
    _ROOT = Path(__file__).resolve().parent.parent
    sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import trace  # noqa: E402

__all__ = ["ArgEvent", "SpanStat", "SpanReduction", "read_events",
           "reduce_spans", "host_pack_s", "host_reads", "evo_grow_rounds",
           "below_partition_share"]

# the gap key where no program span covers a gap (as in ``trace.Reduced``)
NO_SPAN = "host"


@dataclass
class ArgEvent(trace.Event):
    """A trace event with its stats; a program span has a ``cat`` arg."""
    args: dict = field(default_factory=dict)

    @property
    def is_span(self) -> bool:
        return not self.device and "cat" in self.args


@dataclass
class SpanStat:
    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0                 # inside the window, children excluded
    args: Dict[str, float] = field(default_factory=dict)   # numeric, summed


@dataclass
class SpanReduction:
    window_s: float
    idle_s: float
    spans: Dict[str, SpanStat] = field(default_factory=dict)
    idle_spans: Dict[str, float] = field(default_factory=dict)


def read_events(path: str) -> List[ArgEvent]:
    """Every event of an ``.xplane.pb`` file with its stats, on one clock,
    in seconds (``trace.read_events`` plus the args)."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    events: List[ArgEvent] = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            is_dev = plane.name.startswith("/device:")
            for line in plane.lines:
                modules = is_dev and line.name == "XLA Modules"
                for ev in line.events:
                    args = dict(ev.stats)
                    events.append(ArgEvent(
                        name=ev.name, start=ev.start_ns * 1e-9,
                        end=(ev.start_ns + ev.duration_ns) * 1e-9,
                        module=ev.name if modules else str(args.get("hlo_module", "")),
                        device=plane.name if is_dev else "", line=line.name,
                        args=args,
                    ))
    return events


def _numeric(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def reduce_spans(events: List[ArgEvent],
                 window: str = trace.WINDOW) -> Optional[SpanReduction]:
    """The program spans of the window (those overlapping it) and its idle
    gaps keyed by span.  None without a window or a device operation."""
    wins = [e for e in events if e.name == window and not e.device]
    if not wins:
        return None
    win = max(wins, key=lambda e: e.end - e.start)
    t0, t1 = win.start, win.end
    ops, _ = trace._device_events(events)
    if not ops:
        return None
    spans = [e for e in events if getattr(e, "is_span", False)
             and e.end > e.start and e.start < t1 and e.end > t0]
    stats: Dict[str, SpanStat] = defaultdict(SpanStat)
    by_line = defaultdict(list)
    for e in spans:
        st = stats[e.name]
        st.count += 1
        st.total_s += e.end - e.start
        for k, v in e.args.items():
            if k != "cat" and _numeric(v):
                st.args[k] = st.args.get(k, 0.0) + v
        by_line[e.line].append(e)
    for evs in by_line.values():        # spans nest within one thread
        for e, d in trace._self_times(evs, t0, t1):
            stats[e.name].self_s += d
    merged = trace.union(trace.clip(
        ((e.start, e.end) for evs in ops.values() for e in evs), t0, t1))
    gap_list = trace.gaps(merged, t0, t1)
    names = trace._innermost(by_line.get(win.line, []),
                             [0.5 * (a + b) for a, b in gap_list])
    idle = defaultdict(float)
    for (a, b), name in zip(gap_list, names):
        idle[name] += b - a
    return SpanReduction(window_s=t1 - t0, idle_s=trace.total(gap_list),
                         spans=dict(stats), idle_spans=dict(idle))


def below_partition_share(red: SpanReduction) -> float:
    """Share of the idle seconds inside a program span below ``partition``
    (neither outside every span nor directly under ``partition``)."""
    if red.idle_s <= 0:
        return 1.0
    inside = sum(s for k, s in red.idle_spans.items()
                 if k not in (NO_SPAN, "partition"))
    return inside / red.idle_s


def host_pack_s(red: Optional[SpanReduction], units: int) -> Optional[float]:
    """Self seconds of the ``vcycle.pack`` spans in the window, per partition
    (the host pack plan; layer "host pack plan")."""
    st = red.spans.get("vcycle.pack") if red is not None and units else None
    return st.self_s / units if st is not None else None


def _partition_arg(red, units, name) -> Optional[float]:
    st = red.spans.get("partition") if red is not None and units else None
    if st is None or name not in st.args:
        return None
    return st.args[name] / units


def host_reads(red: Optional[SpanReduction], units: int) -> Optional[float]:
    """Blocking device-to-host reads per partition, from the ``host_reads``
    the window's ``partition`` spans close with (layer "host-device
    boundary")."""
    return _partition_arg(red, units, "host_reads")


def evo_grow_rounds(red: Optional[SpanReduction], units: int) -> Optional[float]:
    """Trips through the device GA's grow loop per partition, from the
    window's ``partition`` spans (layer "device GA")."""
    return _partition_arg(red, units, "evo_grow_rounds")


# --------------------------------------------------------------------- probe


def _window(load, traced: bool, spans_on: bool):
    """One round; under the profiler when ``traced``, with the program's
    spans off (``repro.obs`` sees no profiler) unless ``spans_on``."""
    import jax
    from repro.obs import trace as obs_trace

    if not traced:
        return load.run(rounds=1), None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    out = tempfile.mkdtemp(prefix="bench_spans_")
    saved = obs_trace._profiling
    try:
        if not spans_on:
            obs_trace._profiling = lambda: False
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                w = load.run(rounds=1)
        finally:
            jax.profiler.stop_trace()
            obs_trace._profiling = saved
        files = sorted(Path(out).rglob("*.xplane.pb"))
        events = read_events(str(files[-1])) if files else []
    finally:
        shutil.rmtree(out, ignore_errors=True)
    return w, events


def _round_line(r: int, windows: dict, events, units: int) -> dict:
    line = {"round": r, **{f"{m}_s": w.elapsed for m, w in windows.items()}}
    if events is None:
        return line
    red = reduce_spans(events)
    base = trace.reduce_events(events)
    if red is None or base is None:
        return line
    calls = sorted((e for e in events if getattr(e, "is_span", False)
                    and e.name == "partition"), key=lambda e: e.start)
    reads = defaultdict(float)          # host.read seconds by what was read
    for e in events:
        if getattr(e, "is_span", False) and e.name == "host.read":
            reads[str(e.args.get("what"))] += e.end - e.start
    line.update(
        busy_s=base.busy_s, window_s=red.window_s, idle_s=red.idle_s,
        idle_below_partition=below_partition_share(red),
        partition_s=[[int(e.args.get("seed", -1)), e.end - e.start] for e in calls],
        host_pack_s=host_pack_s(red, units), host_reads=host_reads(red, units),
        evo_grow_rounds=evo_grow_rounds(red, units),
        evo_grow_budget=_partition_arg(red, units, "evo_grow_budget"),
        idle_spans=trace.top(red.idle_spans, 20),
        self_s=trace.top({k: v.self_s for k, v in red.spans.items()}, 20),
        reads_s=trace.top(reads, 10),
        counts={k: v.count for k, v in red.spans.items()},
        gc=[red.spans["py.gc"].count, red.spans["py.gc"].total_s]
        if "py.gc" in red.spans else [0, 0.0],
    )
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="bench/configs/<name>.json")
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--modes", default="untraced,profiler,traced",
                    help="per round, in order: untraced (no profiler), "
                         "profiler (spans off), traced (spans on)")
    ap.add_argument("--scale", type=int, default=0,
                    help="replace the configuration's scale (a rehearsal)")
    ap.add_argument("--out", default="", help="also append the lines here")
    args = ap.parse_args(argv)

    from bench import drive, harness
    from bench.data import graphs

    harness.enable_cache()
    config = json.loads((harness.BENCH / "configs" / f"{args.config}.json").read_text())
    if args.scale:
        config["scale"] = args.scale
    traffic = json.loads((harness.BENCH / "traffic" / "offline.json").read_text())
    t0 = time.perf_counter()
    g = graphs.build(config, int(config["graph_seed"]))
    load = drive.Offline(drive.program(), g, config, traffic, args.seed)
    load.setup()
    print(json.dumps({"config": args.config, "setup_s": time.perf_counter() - t0,
                      "device": harness.device_info()}), flush=True)
    modes = [m for m in args.modes.split(",") if m]
    for r in range(args.rounds):
        windows, events = {}, None
        for m in modes:
            w, ev = _window(load, m != "untraced", m == "traced")
            windows[m] = w
            if m == "traced":
                events = ev
            if w.failed:
                print(f"bench: {w.failed} partition() calls raised", file=sys.stderr)
                return 1
        line = json.dumps(_round_line(r, windows, events, len(load.seeds)))
        print(line, flush=True)
        if args.out:
            Path(args.out).parent.mkdir(parents=True, exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
