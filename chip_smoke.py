#!/usr/bin/env python3
"""Smoke run of the partitioner's main path on one accelerator.

    python chip_smoke.py                        # R-MAT scale 18, k=32
    python chip_smoke.py --chips 4 --scale 12   # distributed LP + sharded GA
    python chip_smoke.py --scale 12             # small rehearsal anywhere

The graph is ``rmat(scale, 16, seed=1)`` (Graph500 Kronecker parameters),
partitioned at the paper's web-graph setting, k=32 and eps=0.03.  The
default is scale 18 (262,144 nodes, 7,611,866 arcs): scale 20 (31.4M arcs)
fits one TPU v5e's memory but not the 20 minutes a run may take (PERF.md
has the timings).  One process runs three phases through the public entry
points, and prints the seconds of each span of the repo's tracer in the
first two:

1. partition -- one cold ``partition()`` with default engines; feasible,
   beats hash partitioning, and the device V-cycle ran;
2. serve -- a ``PartitionSession`` started from phase 1's labels absorbs 8
   batches of 1,024 edge additions and 1,024 removals; one invariant audit,
   and the served cut equals the host cut of the downloaded graph;
3. kernel -- one dense Pallas scoring pass (``node_scores``) equals the CSR
   oracle ``node_scores_ref``.

Every failed check raises.  The last line of stdout is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``; ``ok`` is true only
when every phase passed on a TPU.  Without an accelerator the full-size run
is refused before any work (exit 1, nothing on stdout); ``--scale`` runs the
phases anyway as a rehearsal that ends with ``"ok": false``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

K = 32
EPS = 0.03
SEED = 0
GRAPH_SEED = 1
EDGE_FACTOR = 16
DEFAULT_SCALE = 18
SERVE_BATCHES = 8
SERVE_BATCH_EDGES = 1024
DIST_CUT_BAND = 1.10


class SmokeFailure(AssertionError):
    """A phase check did not hold."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"{time.perf_counter() - _T0:8.1f}s {msg}", flush=True)


class CompileClock:
    """Sums JAX's tracing, lowering and backend-compile durations while
    entered, and logs every backend compile that takes a second or more.
    Each duration is kept with the time it ended, so that a span can tell
    the compile seconds spent inside it from its run seconds."""

    EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        self.seconds = 0.0
        self.events = []        # (perf_counter at the end, seconds)

    def _on_event(self, name: str, secs: float, fun_name: str = "?",
                  **_kw) -> None:
        if name in self.EVENTS:
            self.seconds += secs
            self.events.append((time.perf_counter(), secs))
            if name == self.EVENTS[-1] and secs >= 1.0:
                log(f"[compile] {fun_name} {secs:.1f}s")

    def __enter__(self):
        import jax

        from repro.obs import Tracer, set_tracer

        self.origin = time.perf_counter()   # the tracer's ts are from here
        self.tracer = Tracer()
        set_tracer(self.tracer)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax

        from repro.obs import set_tracer

        jax.monitoring.unregister_event_duration_listener(self._on_event)
        set_tracer(None)

    def log_spans(self, phase: str, t0: float) -> dict:
        """Log, per span name of the repo's tracer that closed since ``t0``
        (a ``perf_counter`` reading), its calls, its wall seconds and the
        compile seconds that ended inside it.  Spans that wrap device work
        block until it is done, so a span's seconds hold its device time."""
        rows = {}
        for ev in self.tracer.events:
            if ev.get("ph") != "X":
                continue
            a = self.origin + ev["ts"] / 1e6
            if a < t0:
                continue
            b = a + ev["dur"] / 1e6
            row = rows.setdefault(ev["name"], [0, 0.0, 0.0])
            row[0] += 1
            row[1] += b - a
            row[2] += sum(s for t, s in self.events if a <= t <= b)
        for name, (calls, secs, comp) in sorted(rows.items()):
            log(f"[{phase} spans] {name} calls={calls} wall_s={secs:.2f} "
                f"compile_s={comp:.2f} run_s={secs - comp:.2f}")
        return rows


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory(device=None) -> dict:
    import jax

    stats = (device or jax.devices()[0]).memory_stats() or {}
    return {key: stats.get(key) for key in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def make_graph(scale: int):
    from repro.graph import rmat

    t0 = time.perf_counter()
    g = rmat(scale, EDGE_FACTOR, seed=GRAPH_SEED)
    log(f"[graph] rmat({scale}, {EDGE_FACTOR}, seed={GRAPH_SEED}): "
        f"n={g.n} m={g.m} arcs, {time.perf_counter() - t0:.1f}s on host")
    return g


def phase_capacity(g, on_tpu: bool) -> dict:
    from repro.core import LPEngine

    wf = LPEngine.will_fit(g.n, g.m, K)
    log(f"[capacity] will_fit: fits={wf['fits']} "
        f"required={wf['required_bytes']} budget={wf['budget_bytes']} "
        f"estimate_total={wf['estimate']['total']}")
    if on_tpu:
        check(wf["fits"] is True, f"will_fit on the TPU: {wf['fits']}")
    return wf


def phase_partition(g, clock: CompileClock) -> dict:
    """One cold ``partition()``: its seconds split into compile and run."""
    from repro.core import PartitionerConfig, cut_np, hash_partition, partition

    c0 = clock.seconds
    t0 = time.perf_counter()
    rep = partition(g, PartitionerConfig(k=K, eps=EPS, seed=SEED))
    cold_s = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    spans = clock.log_spans("partition", t0)
    hash_cut = cut_np(g, hash_partition(g.n, K))
    st = rep.engine_stats or {}
    mem = memory()
    log(f"[partition] cut={rep.cut:.0f} hash_cut={hash_cut:.0f} "
        f"imbalance={rep.imbalance:.5f} feasible={rep.feasible} "
        f"levels={rep.level_sizes}")
    log(f"[partition] cold_s={cold_s:.2f} compile_s={compile_s:.2f} "
        f"steady_s={cold_s - compile_s:.2f} (cold minus compile) "
        f"peak_bytes_in_use={mem['peak_bytes_in_use']}")
    log(f"[partition] sweep_calls={st.get('sweep_calls')} "
        f"contract_calls={st.get('contract_calls')} "
        f"evo_calls={st.get('evo_calls')} "
        f"sweep_compiles={st.get('sweep_compiles')} "
        f"contract_compiles={st.get('contract_compiles')} "
        f"chunk_bucket={st.get('chunk_bucket')}")
    check(rep.feasible, "partition infeasible")
    check(rep.imbalance <= EPS + 1e-9, f"imbalance {rep.imbalance} > {EPS}")
    check(rep.cut < hash_cut, f"cut {rep.cut} not below hash cut {hash_cut}")
    for name in ("sweep_calls", "contract_calls", "evo_calls"):
        check(st.get(name, 0) > 0, f"device path did not run: {name}=0")
    return dict(report=rep, cut=rep.cut, hash_cut=hash_cut,
                imbalance=rep.imbalance, cold_s=cold_s,
                steady_s=cold_s - compile_s, compile_s=compile_s,
                peak_bytes_in_use=mem["peak_bytes_in_use"], spans=spans)


def phase_serve(g, rep, clock: CompileClock) -> dict:
    """Session from phase 1's labels, churn batches, audit, cut parity."""
    import numpy as np

    from repro.core import cut_np
    from repro.dynamic import PartitionSession, SessionConfig, churn_updates
    from repro.resilience import InvariantAuditor

    sess = PartitionSession.from_restored(
        g, SessionConfig(k=K, eps=EPS, seed=SEED), labels=rep.labels,
        step=0, cut_ref=rep.cut, ew_ref=float(g.ew.sum()) / 2.0,
    )
    stream = churn_updates(g, SERVE_BATCH_EDGES, np.random.default_rng(SEED))
    seconds = []
    c0 = clock.seconds
    t_serve = time.perf_counter()
    for _ in range(SERVE_BATCHES):
        t0 = time.perf_counter()
        res = sess.update(next(stream))
        seconds.append(time.perf_counter() - t0)
        check(res.feasible, f"update {res.step} left the partition infeasible")
    compile_s = clock.seconds - c0
    spans = clock.log_spans("serve", t_serve)
    audit = InvariantAuditor(sess).audit()
    host_cut = cut_np(sess.store.graph().to_host(), sess.labels_np())
    mem = memory()
    steady = statistics.median(seconds[1:]) if len(seconds) > 1 else seconds[0]
    log(f"[serve] batches={SERVE_BATCHES} x ({SERVE_BATCH_EDGES} adds + "
        f"{SERVE_BATCH_EDGES} removals) first_s={seconds[0]:.3f} "
        f"steady_median_s={steady:.3f} compile_s={compile_s:.2f} "
        f"escalations={sess.escalations}")
    log(f"[serve] cut={sess.cut:.0f} host_cut={host_cut:.0f} "
        f"imbalance={sess.imbalance:.5f} audit_ok={audit.ok} "
        f"failures={audit.failures} "
        f"peak_bytes_in_use={mem['peak_bytes_in_use']}")
    check(audit.ok, f"invariant audit failed: {audit.failures}")
    check(sess.cut == host_cut, f"served cut {sess.cut} != host cut {host_cut}")
    return dict(cut=sess.cut, host_cut=host_cut, first_s=seconds[0],
                steady_s=steady, audit_ok=audit.ok, spans=spans)


def phase_kernel(g, labels, clock: CompileClock) -> dict:
    """One dense Pallas scoring pass against the CSR oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.lp_score import default_interpret, node_scores, node_scores_ref

    interpret = default_interpret()
    c0 = clock.seconds
    t0 = time.perf_counter()
    got = jax.block_until_ready(node_scores(g, labels, K))
    first_s = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    t0 = time.perf_counter()
    got = jax.block_until_ready(node_scores(g, labels, K))
    steady_s = time.perf_counter() - t0
    ref = node_scores_ref(
        jnp.asarray(g.indptr, jnp.int32), jnp.asarray(g.indices, jnp.int32),
        jnp.asarray(g.ew, jnp.float32), jnp.asarray(labels, jnp.int32), K,
    )
    # integral weights and per-node sums far below 2**24: both sides are
    # exact f32 integer sums, so the stated tolerance is zero
    diff = float(jnp.max(jnp.abs(got - ref)))
    log(f"[kernel] node_scores interpret={interpret} shape={tuple(got.shape)} "
        f"max_abs_diff={diff} first_s={first_s:.2f} compile_s={compile_s:.2f} "
        f"steady_s={steady_s:.3f}")
    check(got.shape == (g.n, K), f"scores shape {got.shape}")
    check(bool(np.isfinite(np.asarray(got)).all()), "non-finite scores")
    check(diff == 0.0, f"node_scores differs from node_scores_ref by {diff}")
    return dict(max_abs_diff=diff, interpret=interpret, first_s=first_s,
                steady_s=steady_s)


def phase_four_chips(g) -> dict:
    """Distributed LP partition and island-sharded GA vs one-device runs.

    The dist run goes first, so the per-device peaks logged after it are its
    own; the one-device ``engine="auto"`` run with the same (default)
    settings is its reference.  The GA pair runs one island per device."""
    import jax
    import numpy as np

    from repro.core import PartitionerConfig, partition

    D = jax.device_count()
    check(D == 4, f"--chips 4 needs 4 devices, found {D}")

    def timed(**kw):
        t0 = time.perf_counter()
        rep = partition(g, PartitionerConfig(k=K, eps=EPS, seed=SEED, **kw))
        return rep, time.perf_counter() - t0

    dist, dist_s = timed(engine="dist", dist_shards=D)
    for i, d in enumerate(jax.devices()):
        log(f"[devices after dist] {i} {d.device_kind}: {memory(d)}")
    single, single_s = timed()
    ratio = dist.cut / single.cut
    log(f"[dist] single cut={single.cut:.0f} s={single_s:.2f} | dist cut="
        f"{dist.cut:.0f} imbalance={dist.imbalance:.5f} "
        f"feasible={dist.feasible} s={dist_s:.2f} ratio={ratio:.4f} "
        f"levels={dist.level_sizes}")
    check(dist.feasible, "dist partition infeasible")
    check(dist.imbalance <= EPS + 1e-9, f"dist imbalance {dist.imbalance}")
    check(ratio <= DIST_CUT_BAND, f"dist cut ratio {ratio:.4f} > {DIST_CUT_BAND}")

    evo = dict(islands=D, generations=2)
    flat, flat_s = timed(**evo)
    shard, shard_s = timed(evo_shard_islands=True, **evo)
    same = bool(np.array_equal(flat.labels, shard.labels))
    evo_calls = [(r.engine_stats or {}).get("evo_calls", 0)
                 for r in (flat, shard)]
    log(f"[islands] unsharded cut={flat.cut:.0f} s={flat_s:.2f} | sharded "
        f"cut={shard.cut:.0f} s={shard_s:.2f} identical={same} "
        f"evo_calls={evo_calls}")
    check(min(evo_calls) > 0, f"the device GA did not run: {evo_calls}")
    check(same, "island-sharded GA labels differ from unsharded")
    for i, d in enumerate(jax.devices()):
        log(f"[devices at end] {i} {d.device_kind}: {memory(d)}")
    return dict(dist_cut=dist.cut, single_cut=single.cut, ratio=ratio,
                islands_identical=same)


def run(scale: int = DEFAULT_SCALE, chips: int = 1) -> dict:
    """Run the phases in this process; raises SmokeFailure on a failed check."""
    info = device_info()
    on_tpu = info["platform"] == "tpu"
    g = make_graph(scale)
    if chips == 4:
        return dict(device=info, on_tpu=on_tpu, four=phase_four_chips(g))
    with CompileClock() as clock:
        capacity = phase_capacity(g, on_tpu)
        part = phase_partition(g, clock)
        serve = phase_serve(g, part["report"], clock)
        kernel = phase_kernel(g, part["report"].labels, clock)
    if on_tpu:
        check(kernel["interpret"] is False, "Pallas ran in interpret mode")
    return dict(device=info, on_tpu=on_tpu, capacity=capacity,
                partition=part, serve=serve, kernel=kernel)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=int, default=None,
                    help=f"R-MAT scale (default {DEFAULT_SCALE}); setting it "
                    "allows a rehearsal on a host without an accelerator")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    from repro import compile_cache

    info = device_info()
    if info["platform"] == "cpu" and args.scale is None:
        print("chip_smoke: JAX found no accelerator; pass --scale to "
              "rehearse on the CPU", file=sys.stderr)
        return 1
    cache = compile_cache.enable()
    log(f"[device] {info} jax={jax.__version__} cache={cache}")
    t0 = time.perf_counter()
    out = run(args.scale if args.scale is not None else DEFAULT_SCALE,
              args.chips)
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": out["on_tpu"], "device": out["device"]}), flush=True)
    return 0 if out["on_tpu"] else 1


if __name__ == "__main__":
    sys.exit(main())
