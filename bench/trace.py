"""Reduction of a ``jax.profiler`` trace to device busy time, idle gaps and
device seconds per jitted function.

The reduction is generic: a per-layer reader names the jitted functions it
wants (``"_lp_sweep"``), and :meth:`Reduced.device_s` sums the device time of
every executable built from them.  Nothing here knows a kernel.

Where the events come from:

* device planes (``/device:TPU:0``, ...): the ``XLA Ops`` line holds one
  event per operation run on the device, the ``XLA Modules`` line one event
  per executable run (named ``jit_<function>(<id>)``);
* a trace recorded on the CPU has no device plane; there the operations run
  on host threads and carry an ``hlo_module`` stat, and those are taken as
  the device's (used by the tests only: a CPU run is never a device metric).

The window is the host event named :data:`WINDOW` that the harness opens
around the measured work.  Idle gaps are the stretches of the window in
which no operation ran on a device; each is named by the innermost host
event (on the window's own thread) that covers its middle.
"""

from __future__ import annotations

import bisect
import re
import warnings
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "WINDOW", "Reduced", "union", "total", "gaps", "clip", "module_fn",
    "op_name", "reduce_events", "reduce_file", "read_events", "top",
]

WINDOW = "bench.window"

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of the given (start, end) intervals."""
    out: List[List[float]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return float(sum(b - a for a, b in intervals))


def clip(intervals: Iterable[Interval], t0: float, t1: float) -> List[Interval]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if min(b, t1) > max(a, t0)]


def gaps(merged: Sequence[Interval], t0: float, t1: float) -> List[Interval]:
    """The parts of [t0, t1] that no interval of ``merged`` (sorted and
    disjoint, as :func:`union` returns) covers."""
    out, cur = [], t0
    for a, b in merged:
        if a > cur:
            out.append((cur, min(a, t1)))
        cur = max(cur, b)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [g for g in out if g[1] > g[0]]


_SUFFIX = re.compile(r"\(\d+\)$")


def module_fn(module: str) -> str:
    """``jit__lp_sweep(12)`` -> ``_lp_sweep``; other names pass unchanged
    but for the ``(id)`` suffix."""
    name = _SUFFIX.sub("", module.strip())
    return name[4:] if name.startswith("jit_") else name


def op_name(name: str) -> str:
    """``%while.52 = (s32[], ...) while(...)`` -> ``while.52``: a device op
    event may be named by its whole HLO instruction."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


@dataclass
class Event:
    name: str
    start: float        # seconds, on the trace's common clock
    end: float
    module: str = ""    # executable an operation belongs to, where known
    device: str = ""    # device plane name; "" for host events
    line: str = ""


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over devices of the busy union
    devices: int
    module_s: dict = field(default_factory=dict)   # function -> device s
    op_s: dict = field(default_factory=dict)       # "fn/op" -> device s
    idle: dict = field(default_factory=dict)       # host activity -> gap s
    gap_count: int = 0

    def device_s(self, functions: Iterable[str]) -> float:
        """Device seconds of the executables built from ``functions``,
        averaged over the devices traced."""
        return float(sum(self.module_s.get(f, 0.0) for f in set(functions)))

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0


def read_events(path: str) -> List[Event]:
    """Every event of an ``.xplane.pb`` file, on one clock, in seconds."""
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    events: List[Event] = []
    with warnings.catch_warnings():
        # the stats' builtin type warns that it has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            is_dev = plane.name.startswith("/device:")
            for line in plane.lines:
                modules = is_dev and line.name == "XLA Modules"
                for ev in line.events:
                    module = ev.name if modules else str(
                        dict(ev.stats).get("hlo_module", ""))
                    events.append(Event(
                        name=ev.name, start=ev.start_ns * 1e-9,
                        end=(ev.start_ns + ev.duration_ns) * 1e-9,
                        module=module, device=plane.name if is_dev else "",
                        line=line.name,
                    ))
    return events


def _device_events(events: List[Event]):
    """(ops, modules) per device.  Device planes give both lines; a trace
    without device planes (the CPU) gives host events with an hlo_module."""
    ops, mods = defaultdict(list), defaultdict(list)
    have_dev = any(e.device for e in events)
    for e in events:
        if have_dev:
            if not e.device:
                continue
            if e.line == "XLA Ops":
                ops[e.device].append(e)
            elif e.line == "XLA Modules":
                mods[e.device].append(e)
        elif e.module and e.end > e.start:
            ops["cpu"].append(e)
    for dev in list(mods):
        ops.setdefault(dev, mods[dev])  # no op line: modules mark busy time
        # an operation belongs to the executable whose run contains it
        ms = sorted(mods[dev], key=lambda e: e.start)
        starts = [e.start for e in ms]
        for e in ops[dev]:
            i = bisect.bisect_right(starts, e.start) - 1
            if not e.module and i >= 0 and ms[i].end >= e.start:
                e.module = ms[i].name
    return ops, mods


def _self_times(evs: List[Event], t0: float, t1: float):
    """(event, seconds inside [t0, t1] not covered by events nested in it):
    a ``while`` op's events enclose its body's, and each second is counted
    once, for the innermost op running then."""
    out = []
    stack: List[list] = []          # [event, self seconds so far]
    for e in sorted(evs, key=lambda x: (x.start, -(x.end - x.start))):
        while stack and stack[-1][0].end <= e.start:
            out.append(tuple(stack.pop()))
        d = max(0.0, min(e.end, t1) - max(e.start, t0))
        if stack and e.end <= stack[-1][0].end:
            stack[-1][1] -= d       # nested: the parent loses these seconds
        stack.append([e, d])
    out.extend(tuple(x) for x in stack)
    return [(e, d) for e, d in out if d > 0]


def _innermost(host: List[Event], points: List[float]) -> List[str]:
    """Name of the innermost host event covering each point ("host" where
    none does).  Events of one thread nest, so a stack swept in time order
    holds, at each point, the chain of events open there."""
    evs = sorted(host, key=lambda e: (e.start, -(e.end - e.start)))
    out = ["host"] * len(points)
    stack: List[Event] = []
    i = 0
    for j in sorted(range(len(points)), key=points.__getitem__):
        t = points[j]
        while i < len(evs) and evs[i].start <= t:
            while stack and stack[-1].end < evs[i].start:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1].end < t:
            stack.pop()
        if stack:
            out[j] = stack[-1].name
    return out


def reduce_events(events: List[Event], window: str = WINDOW) -> Optional[Reduced]:
    """Reduce a trace's events to the window's busy time, idle gaps and
    device seconds per function.  None when the trace holds no window or
    no device operation inside it."""
    wins = [e for e in events if e.name == window and not e.device]
    if not wins:
        return None
    win = max(wins, key=lambda e: e.end - e.start)
    t0, t1 = win.start, win.end
    ops, mods = _device_events(events)
    if not ops:
        return None
    busy, module_s, op_s = [], defaultdict(float), defaultdict(float)
    merged_all: List[Interval] = []
    for dev, evs in ops.items():
        merged = union(clip(((e.start, e.end) for e in evs), t0, t1))
        busy.append(total(merged))
        merged_all.extend(merged)
        # per-function seconds from the module line where there is one
        by_module = mods.get(dev) or evs
        for e in by_module:
            d = min(e.end, t1) - max(e.start, t0)
            if d > 0:
                module_s[module_fn(e.module or e.name)] += d
        for e, d in _self_times(evs, t0, t1):
            fn = module_fn(e.module) if e.module else "?"
            op_s[f"{fn}/{op_name(e.name)}"] += d
    n_dev = len(ops)
    if sum(busy) <= 0:
        return None
    # idle gaps of the union over all devices, named by the host's activity
    gap_list = gaps(union(merged_all), t0, t1)
    host = [e for e in events if not e.device and e.line == win.line
            and e.end > e.start and e is not win]
    names = _innermost(host, [0.5 * (a + b) for a, b in gap_list])
    idle = defaultdict(float)
    for (a, b), name in zip(gap_list, names):
        idle[name] += b - a
    return Reduced(
        window_s=t1 - t0, busy_s=sum(busy) / n_dev, devices=n_dev,
        module_s={k: v / n_dev for k, v in module_s.items()},
        op_s={k: v / n_dev for k, v in op_s.items()},
        idle=dict(idle), gap_count=len(gap_list),
    )


def reduce_file(path: str, window: str = WINDOW) -> Optional[Reduced]:
    return reduce_events(read_events(path), window)


def top(d: dict, n: int = 10) -> list:
    """The ``n`` largest entries of a name -> seconds dict, as [name, s]."""
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
