"""Observability subsystem (ISSUE 9): metrics registry, tracer spans,
compile watchdog, SLO export, and the no-behavior-change guarantees.

The contract under test: tracing on/off and strict-watchdog mode leave
serving labels BIT-identical across the solo, view (throughput preset),
group, and resilient paths — observability observes, it never steers.
The watchdog's sealed mode catches an intentionally unregistered
recompile; the AST static check proves every ``jax.jit`` / ``pallas_call``
callsite under ``src/repro`` is registered in the manifest; the registry
round-trips the legacy stats attribute surface; and the exporters emit
Perfetto-loadable Chrome traces and Prometheus 0.0.4 text.
"""

import json
import os

import numpy as np
import pytest

from repro.dynamic import (
    GraphUpdate,
    PartitionSession,
    SessionConfig,
    SessionGroup,
)
from repro.graph import barabasi_albert
from repro.obs import (
    CompileWatchdog,
    MetricsRegistry,
    RegistryBackedStats,
    Tracer,
    WatchdogError,
    get_tracer,
    set_tracer,
    slo_snapshot,
    span,
    to_prometheus,
    watchdog,
    write_slo,
)
from repro.obs.static_check import check_registration, find_jit_sites
from repro.obs.watchdog import KNOWN_JIT_SITES

pytestmark = pytest.mark.obs


# ------------------------------------------------------------------ registry


def test_registry_counter_lifecycle():
    reg = MetricsRegistry("t")
    reg.counter("a")
    reg.counter("a", 99)            # idempotent declare: never clobbers
    assert reg.get("a") == 0
    reg.inc("a")
    reg.inc("a", 3)
    assert reg.get("a") == 4
    reg.set_counter("a", 7)
    assert reg.get("a") == 7
    with pytest.raises(KeyError):
        reg.get("undeclared")
    reg.gauge("g", 2.5)
    assert reg.get_gauge("g") == 2.5
    reg.series_inc("span_ms", {"phase": "repair"}, 3)
    reg.reset()
    assert reg.get("a") == 0        # counters survive reset as zeros
    assert reg.get_gauge("g", -1.0) == -1.0
    snap = reg.snapshot()
    assert snap["scope"] == "t"
    assert snap["counters"] == {"a": 0}
    assert snap["series"] == []


def test_registry_histogram_log2_buckets_and_quantiles():
    reg = MetricsRegistry()
    for v in [0.001] * 98 + [0.5, 2.0]:
        reg.observe("lat", v)
    h = reg.histogram("lat")
    assert h.count == 100
    # log2 buckets are upper bounds: p50 lands in 0.001's bucket, the
    # 2.0 outlier defines p99's upper bound
    assert 0.001 <= h.quantile(0.50) <= 0.002048
    assert h.quantile(0.99) >= 0.5
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["min"] == 0.001 and snap["max"] == 2.0
    assert abs(snap["sum"] - (0.098 + 2.5)) < 1e-9


def test_registry_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("sweep_compiles", 3)
    reg.gauge("view_hit_ratio", 0.75)
    reg.observe("update_seconds", 0.010)
    reg.observe("update_seconds", 0.020)
    reg.series_inc("span_ms", {"phase": "repair"}, 12)
    text = reg.to_prometheus(prefix="repro_")
    assert "# TYPE repro_sweep_compiles counter" in text
    assert "repro_sweep_compiles 3" in text
    assert "# TYPE repro_view_hit_ratio gauge" in text
    assert "# TYPE repro_update_seconds histogram" in text
    assert 'repro_update_seconds_bucket{le="+Inf"} 2' in text
    assert "repro_update_seconds_count 2" in text
    assert 'repro_span_ms{phase="repair"} 12' in text


def test_registry_backed_stats_attribute_surface():
    class _St(RegistryBackedStats):
        _COUNTER_FIELDS = ("calls", "compiles")
        _SET_FIELDS = ("buckets",)

    st = _St()
    st.calls += 1
    st.calls += 1
    st.compiles = 5
    st.buckets.add(("k", 4))
    assert st.calls == 2 and st.compiles == 5
    assert st.registry.get("calls") == 2      # round-trips the registry
    (key,) = st.buckets                        # sets stay real sets
    assert key == ("k", 4)
    assert st.snapshot() == {"calls": 2, "compiles": 5, "buckets_count": 1}
    st.reset()
    assert st.calls == 0 and not st.buckets
    with pytest.raises(AttributeError):
        st.nope


def test_registry_backed_stats_shared_registry():
    reg = MetricsRegistry("stack")

    class _A(RegistryBackedStats):
        _COUNTER_FIELDS = ("x",)

    class _B(RegistryBackedStats):
        _COUNTER_FIELDS = ("y",)

    a, b = _A(reg), _B(reg)
    a.x += 1
    b.y += 2
    assert reg.snapshot()["counters"] == {"x": 1, "y": 2}


# -------------------------------------------------------------------- tracer


def test_span_disabled_is_shared_noop_and_records_nothing():
    prev = set_tracer(None)
    try:
        s1 = span("a.b", cat="a", n=1)
        s2 = span("c.d")
        assert s1 is s2                 # the cached singleton: no allocation
        with s1 as sp:
            sp.sync_on(np.zeros(2))     # all no-ops
            sp.set(x=1)
    finally:
        set_tracer(prev)


def test_tracer_records_nested_spans_and_exports_chrome(tmp_path):
    tracer = Tracer()
    prev = set_tracer(tracer)
    try:
        with span("outer.op", cat="outer", n=3):
            with span("inner.op") as sp:
                sp.set(hit=True)
    finally:
        set_tracer(prev)
    assert [e["name"] for e in tracer.events] == ["inner.op", "outer.op"]
    outer = tracer.events[1]
    assert outer["ph"] == "X" and outer["cat"] == "outer"
    assert outer["dur"] >= tracer.events[0]["dur"]
    assert outer["args"] == {"n": 3}
    assert tracer.events[0]["args"] == {"hit": True}
    path = tracer.export_chrome(str(tmp_path / "t.trace.json"))
    doc = json.load(open(path))
    assert isinstance(doc["traceEvents"], list) and len(doc["traceEvents"]) == 2
    for ev in doc["traceEvents"]:       # the Perfetto-required fields
        assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(ev)


def test_tracer_disabled_instance_returns_noop():
    tracer = Tracer(enabled=False)
    prev = set_tracer(tracer)
    try:
        with span("x.y"):
            pass
    finally:
        set_tracer(prev)
    assert tracer.events == []


# ------------------------------------------------- spans on the profiler clock


def _profiled(fn, tmp_dir):
    """Run ``fn`` under ``jax.profiler``; returns its result and the program
    spans of the trace as ``(name, args)`` (host events with a ``cat``)."""
    import pathlib
    import warnings

    import jax

    jax.profiler.start_trace(str(tmp_dir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(pathlib.Path(tmp_dir).rglob("*.xplane.pb"))[-1]
    spans = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
            for line in plane.lines:
                for ev in line.events:
                    st = dict(ev.stats)
                    if "cat" in st and not plane.name.startswith("/device:"):
                        spans.append((ev.name, st))
    return out, spans


# the taxonomy of a partition() call, with the args each span carries
_PARTITION_SPANS = {
    "partition": {"n", "m", "k", "seed", "vcycles", "host_reads",
                  "evo_grow_rounds", "evo_grow_budget", "evo_table_steps"},
    "vcycle.level": {"level", "n", "m"},
    "vcycle.pack": {"mode", "n"},
    "vcycle.sweep": {"mode", "n", "m", "iters", "chunks"},
    "vcycle.contract": {"n", "m"},
    "vcycle.evolve": {"device", "n", "m", "islands", "pop", "generations",
                      "grow_budget"},
    "vcycle.uncoarsen": {"n", "m"},
    "vcycle.project": {"n"},
    "partition.finalize": {"cycle"},
    "host.read": {"what", "bytes"},
    "py.gc": {"generation", "collected"},
}


def _small_partition():
    """An engine-path partition() that coarsens (a few seconds on a CPU)."""
    from repro.core import PartitionerConfig, partition
    from repro.graph import rmat

    return partition(rmat(10, 8, seed=2), PartitionerConfig(
        k=4, seed=1, engine="jnp", coarsest_factor=32))


@pytest.fixture(scope="module")
def partition_runs(tmp_path_factory):
    """The same partition with tracing off, under the profiler alone (with
    ``jax.block_until_ready`` recording any call, and collections frequent
    enough that ``py.gc`` spans appear) and under a Tracer."""
    import gc

    import jax

    prev = set_tracer(None)
    try:
        off = _small_partition()
        blocks = []

        def _block(x):
            blocks.append(x)
            raise AssertionError("sync_on blocked without a Tracer")

        threshold = gc.get_threshold()
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(jax, "block_until_ready", _block)
            gc.set_threshold(50)
            try:
                prof, spans = _profiled(_small_partition,
                                        tmp_path_factory.mktemp("xplane"))
            finally:
                gc.set_threshold(*threshold)
        set_tracer(Tracer())
        traced = _small_partition()
        tracer_events = get_tracer().events
    finally:
        set_tracer(prev)
    return dict(off=off, prof=prof, spans=spans, blocks=blocks,
                traced=traced, tracer_events=tracer_events)


def test_span_under_profiler_is_an_annotation_and_off_again_after(tmp_path):
    prev = set_tracer(None)
    try:
        assert span("a.b") is span("c.d")          # nothing collecting

        def body():
            with span("outer.op", cat="outer", n=3) as sp:
                assert sp is not span("x.y") and sp.active
                sp.sync_on(np.zeros(2))              # never blocks here
                sp.set(hit=7)

        _, spans = _profiled(body, tmp_path)
        assert span("a.b") is span("c.d")          # the profiler stopped
    finally:
        set_tracer(prev)
    assert ("outer.op", {"cat": "outer", "n": 3, "hit": 7}) in spans


def test_partition_spans_reach_the_profiler_with_their_args(partition_runs):
    seen = {}
    for name, args in partition_runs["spans"]:
        seen.setdefault(name, set()).update(args)
    for name, keys in _PARTITION_SPANS.items():
        assert name in seen, name
        assert keys | {"cat"} <= seen[name], (name, seen[name])
    part = [a for n, a in partition_runs["spans"] if n == "partition"]
    stats = partition_runs["prof"].engine_stats
    assert len(part) == 1
    assert part[0]["host_reads"] == stats["host_reads"] > 0
    assert part[0]["evo_grow_rounds"] == stats["evo_grow_rounds"] > 0
    assert part[0]["evo_grow_budget"] == stats["evo_grow_budget"] > 0
    assert part[0]["evo_grow_rounds"] <= part[0]["evo_grow_budget"]
    assert part[0]["evo_table_steps"] == stats["evo_table_steps"]


def test_sync_on_never_blocks_without_a_tracer(partition_runs):
    assert partition_runs["blocks"] == []


def test_partition_labels_bit_identical_with_profiler_and_tracer(partition_runs):
    off = partition_runs["off"].labels
    np.testing.assert_array_equal(off, partition_runs["prof"].labels)
    np.testing.assert_array_equal(off, partition_runs["traced"].labels)


def test_host_reads_same_with_tracing_on_and_off(partition_runs):
    reads = [partition_runs[r].engine_stats["host_reads"]
             for r in ("off", "prof", "traced")]
    assert reads[0] > 0 and reads == [reads[0]] * 3
    rounds = [partition_runs[r].engine_stats["evo_grow_rounds"]
              for r in ("off", "prof", "traced")]
    assert rounds[0] == 0 and rounds[1] == rounds[2] > 0   # read only traced
    names = {e["name"] for e in partition_runs["tracer_events"]}
    assert set(_PARTITION_SPANS) - {"py.gc"} <= names      # Tracer mode too


class _Unreadable:
    """A device scalar stand-in whose every host conversion fails."""

    def _fail(self, *a, **k):
        raise AssertionError("the grow-round scalar was read")

    __array__ = __int__ = __float__ = __index__ = __bool__ = _fail


def test_grow_round_scalar_is_never_read_with_tracing_off(
        monkeypatch, partition_runs):
    from repro.core import evo_device

    seed_step = evo_device.evo_seed_step

    def unreadable_rounds(*a, **k):
        labs, keys, _ = seed_step(*a, **k)
        return labs, keys, _Unreadable()

    monkeypatch.setattr(evo_device, "evo_seed_step", unreadable_rounds)
    prev = set_tracer(None)
    try:
        rep = _small_partition()
    finally:
        set_tracer(prev)
    np.testing.assert_array_equal(rep.labels, partition_runs["off"].labels)
    assert rep.engine_stats["evo_grow_rounds"] == 0
    assert rep.engine_stats["evo_grow_budget"] > 0


@pytest.mark.parametrize("case", ["planted", "mesh"])
def test_evo_grow_rounds_match_the_numpy_oracle(case):
    """The device's grow-loop trips are the max, over the population, of
    the frontier rounds the numpy oracle runs (it exits on the same
    converged / stalled conditions); unread until asked for."""
    from repro.core import LPEngine
    from repro.core.evolutionary import EvoConfig, grow_rounds_bound
    from repro.core.metrics import lmax
    from repro.graph import mesh2d, planted_partition

    g = (planted_partition(600, 4, p_in=0.05, p_out=0.004, seed=1)
         if case == "planted" else mesh2d(24))
    k = 4
    cfg = EvoConfig(k=k, Lmax=lmax(g.n, k, 0.03), islands=2,
                    pop_per_island=2, generations=0, refine_iters=2, seed=9)
    eng = LPEngine(g, seed=0)
    lab = eng.evolve_device(g, cfg)
    assert eng.stats.evo_grow_rounds == 0               # left on the device
    assert eng.stats.evo_grow_budget == grow_rounds_bound(g.n, k, g.m)
    oracle_rounds: list = []
    np.testing.assert_array_equal(
        np.asarray(lab), eng.evolve_oracle(g, cfg, grow_rounds=oracle_rounds))
    assert len(oracle_rounds) == 4 and max(oracle_rounds) > 1
    assert eng.read_grow_rounds() == max(oracle_rounds)
    assert eng.read_grow_rounds() == max(oracle_rounds)  # read once, kept


# ------------------------------------------------------------------ watchdog


def test_watchdog_counts_and_snapshot():
    wd = CompileWatchdog()
    assert wd.note("engine.sweep", ("b", 1)) is True
    assert wd.note("engine.sweep", ("b", 1)) is False   # warm: not a compile
    assert wd.note("engine.sweep", ("b", 2)) is True
    assert wd.compile_count("engine.sweep") == 2
    assert wd.bucket_count("engine.sweep") == 2
    snap = wd.snapshot()
    assert snap["kernels"]["engine.sweep"]["compiles"] == 2
    wd.reset()
    assert wd.compile_count() == 0 and wd.bucket_count() == 0


def test_watchdog_strict_rejects_undeclared_family():
    wd = CompileWatchdog(strict=True)
    wd.note("engine.sweep", ("ok",))            # declared: fine
    with pytest.raises(WatchdogError, match="undeclared kernel family"):
        wd.note("rogue.kernel", ("k",))
    wd.set_strict(False)
    wd.note("rogue.kernel", ("k",))             # lenient: auto-declares


def test_watchdog_seal_catches_unregistered_recompile_unit():
    wd = CompileWatchdog()
    wd.note("engine.repair", ("warm",))
    wd.seal()
    wd.note("engine.repair", ("warm",))         # known bucket: still fine
    with pytest.raises(WatchdogError, match="sealed bucket set"):
        wd.note("engine.repair", ("cold",))
    wd.unseal()
    wd.note("engine.repair", ("cold",))


def test_watchdog_seal_catches_session_recompile():
    """The regression the seal exists for: a serving loop whose next batch
    would trace a NEW shape bucket (here: the very first update of a
    fresh session, whose repair/compact kernels were never compiled at
    this graph size) raises instead of silently recompiling."""
    # unusual n so no earlier test in this process warmed these buckets
    g = barabasi_albert(619, 4, seed=5)
    sess = PartitionSession(g, SessionConfig(k=3, seed=0, repair_iters=1))
    wd = watchdog()
    rng = np.random.default_rng(0)
    u = rng.integers(0, g.n, 37)
    v = (u + 1 + rng.integers(0, g.n - 1, 37)) % g.n
    wd.seal()
    try:
        with pytest.raises(WatchdogError, match="sealed bucket set"):
            sess.update(GraphUpdate.add_edges(u, v))
    finally:
        wd.unseal()
    # with the seal lifted the same update proceeds and registers buckets
    res = sess.update(GraphUpdate.add_edges(u, v))
    assert not res.noop


# ----------------------------------------------------------- bit-parity


def _stream(n, nb, batches, seed):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(batches):
        u = rng.integers(0, n, nb)
        v = (u + 1 + rng.integers(0, n - 1, nb)) % n
        out.append(GraphUpdate.add_edges(u, v))
    return out


def _with_obs(enabled, fn):
    """Run fn() with tracing+strict-watchdog on (enabled=True) or fully
    off (enabled=False); restores global state either way."""
    wd = watchdog()
    prev_strict = wd.strict
    prev = set_tracer(Tracer(enabled=True) if enabled else None)
    wd.set_strict(enabled)
    try:
        return fn()
    finally:
        set_tracer(prev)
        wd.set_strict(prev_strict)


@pytest.mark.parametrize("preset", ["solo", "view"])
def test_tracing_and_strict_mode_label_parity_session(preset):
    """Tracing on (with forced device syncs at span close) + strict
    watchdog vs everything off: the served labels must be bit-identical.
    Covers the default path (compact every step) and the throughput
    preset (overlay view + deferred compaction)."""
    g = barabasi_albert(512, 4, seed=7)

    def run():
        cfg = (SessionConfig(k=4, seed=0, repair_iters=2) if preset == "solo"
               else SessionConfig.throughput(k=4, seed=0))
        sess = PartitionSession(g, cfg)
        for upd in _stream(g.n, 24, 3, seed=13):
            sess.update(upd)
        return sess.labels_np()

    base = _with_obs(False, run)
    traced = _with_obs(True, run)
    np.testing.assert_array_equal(base, traced)


def test_tracing_and_strict_mode_label_parity_group():
    gs = {f"t{i}": barabasi_albert(384, 4, seed=30 + i) for i in range(2)}

    def run():
        tenants = {
            nm: PartitionSession(
                gi, SessionConfig(k=3, seed=i, repair_iters=1))
            for i, (nm, gi) in enumerate(gs.items())
        }
        group = SessionGroup(tenants)
        for s in range(3):
            batch = []
            for nm in gs:
                rng = np.random.default_rng(100 + s)
                u = rng.integers(0, 384, 16)
                v = (u + 1 + rng.integers(0, 383, 16)) % 384
                batch.append((nm, GraphUpdate.add_edges(u, v)))
            group.update_many(batch)
        return {nm: tenants[nm].labels_np() for nm in gs}

    base = _with_obs(False, run)
    traced = _with_obs(True, run)
    for nm in base:
        np.testing.assert_array_equal(base[nm], traced[nm])


def test_vcycle_spans_cover_all_phases():
    """A partition run that actually coarsens (coarsest_factor below n/k)
    emits spans for every V-cycle phase — pack, sweep, contract, project —
    and tracing + strict watchdog leave the result bit-identical."""
    from repro.core import PartitionerConfig, partition

    g = barabasi_albert(4096, 4, seed=5)
    cfg = dict(k=2, seed=0, coarsest_factor=256)

    base = _with_obs(False, lambda: partition(g, PartitionerConfig(**cfg)))

    def run():
        rep = partition(g, PartitionerConfig(**cfg))
        names = {e["name"] for e in get_tracer().events}
        return rep, names

    rep, names = _with_obs(True, run)
    assert {"vcycle.pack", "vcycle.sweep", "vcycle.contract",
            "vcycle.project"} <= names
    np.testing.assert_array_equal(base.labels, rep.labels)


def test_tracing_and_strict_mode_label_parity_resilient():
    from repro.resilience import ResilientConfig, ResilientSession

    g = barabasi_albert(512, 4, seed=9)

    def run():
        sess = PartitionSession(
            g, SessionConfig(k=4, seed=0, repair_iters=1))
        rs = ResilientSession(sess, cfg=ResilientConfig(audit_cadence=2))
        for upd in _stream(g.n, 24, 4, seed=17):
            rs.submit(upd)
        return sess.labels_np()

    base = _with_obs(False, run)
    traced = _with_obs(True, run)
    np.testing.assert_array_equal(base, traced)


# --------------------------------------------------- result timing satellite


def test_update_result_monotonic_timestamp_and_span_breakdown():
    g = barabasi_albert(512, 4, seed=7)
    sess = PartitionSession(g, SessionConfig(k=4, seed=0, repair_iters=1))
    results = [sess.update(upd) for upd in _stream(g.n, 24, 2, seed=13)]
    t_prev = 0.0
    for res in results:
        assert res.t_mono > t_prev       # monotonic across the stream
        t_prev = res.t_mono
        assert res.span_ms               # the always-on phase breakdown
        for phase in ("validate", "store", "compact", "rebuild",
                      "repair", "score"):
            assert phase in res.span_ms
            assert res.span_ms[phase] >= 0.0
        # phases account for (almost all of) the reported latency
        assert sum(res.span_ms.values()) <= res.seconds * 1e3 + 5.0


def test_session_stats_expose_updates_and_view_hits():
    g = barabasi_albert(512, 4, seed=7)
    sess = PartitionSession(g, SessionConfig.throughput(k=4, seed=0))
    for upd in _stream(g.n, 16, 3, seed=19):
        sess.update(upd)
    st = sess.stats()
    assert st["updates_applied"] == 3
    assert 0 <= st["view_hits"] <= 3
    assert sess.metrics.histogram("update_seconds").count == 3


# ---------------------------------------------------------------- SLO export


def test_slo_snapshot_and_prometheus_and_write(tmp_path):
    g = barabasi_albert(512, 4, seed=7)
    sess = PartitionSession(g, SessionConfig(k=4, seed=0, repair_iters=1))
    for upd in _stream(g.n, 16, 2, seed=23):
        sess.update(upd)
    st = sess.stats()
    snap = slo_snapshot(st, [sess.metrics])
    assert snap["slo"]["view_hit_ratio"] == st["view_hits"] / 2
    assert snap["compile_watchdog"]["total_compiles"] >= 0
    assert snap["registries"][0]["scope"] == "session"
    text = to_prometheus(st, [sess.metrics])
    assert "repro_updates_applied 2" in text
    assert "# TYPE repro_update_seconds histogram" in text
    assert "repro_compiles_total" in text
    paths = write_slo(str(tmp_path / "slo"), st, [sess.metrics])
    doc = json.load(open(paths["json"]))
    assert doc["stats"]["updates_applied"] == 2
    prom = open(paths["prom"]).read()
    assert prom.endswith("\n") and "repro_updates_applied" in prom


# -------------------------------------------------------------- static check


def test_every_jit_callsite_is_registered():
    """The tier-1 gate: an unregistered ``jax.jit`` / ``pallas_call``
    callsite under src/repro fails here with its manifest key."""
    root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src", "repro",
    )
    assert check_registration(root) == []


def test_manifest_has_no_stale_entries():
    """Deleted/renamed callsites must leave the manifest too, or the
    registration list rots into documentation."""
    root = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src", "repro",
    )
    live = set(find_jit_sites(root))
    stale = sorted(set(KNOWN_JIT_SITES) - live)
    assert stale == []


# ------------------------------------------------- alloc-site check (PR 10)


def _src_root():
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "src", "repro",
    )


def test_every_alloc_site_is_registered():
    """Memory-accounting gate: every eager device-allocation site in the
    accounted modules must map to a buffer family (or carry an ``exempt:``
    reason) in ``KNOWN_ALLOC_SITES`` — a new persistent buffer cannot land
    unaccounted."""
    from repro.obs.static_check import check_alloc_registration

    assert check_alloc_registration(_src_root()) == []


def test_alloc_manifest_has_no_stale_entries():
    from repro.obs.memory import KNOWN_ALLOC_SITES, MEMORY_FAMILIES
    from repro.obs.static_check import find_alloc_sites

    live = set(find_alloc_sites(_src_root()))
    stale = sorted(set(KNOWN_ALLOC_SITES) - live)
    assert stale == []
    # every manifest value is a real family or an explained exemption
    for site, fam in KNOWN_ALLOC_SITES.items():
        assert fam in MEMORY_FAMILIES or fam.startswith("exempt:"), (
            f"{site}: {fam!r}"
        )
