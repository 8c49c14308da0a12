"""The profiler-trace reduction, the table of peaks and the per-layer readers."""

import json

import numpy as np
import pytest

from bench import harness, trace
from bench.trace import Event


def test_union_total_clip_gaps():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (6, 9), (10, 10)])
    assert merged == [(0, 3), (5, 9)]
    assert trace.total(merged) == 7
    assert trace.clip(merged, 2, 6) == [(2, 3), (5, 6)]
    assert trace.gaps(merged, -1, 12) == [(-1, 0), (3, 5), (9, 12)]
    assert trace.gaps(merged, 1, 8) == [(3, 5)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def test_module_names():
    assert trace.module_fn("jit__lp_sweep(12)") == "_lp_sweep"
    assert trace.module_fn("jit_contract_device") == "contract_device"
    assert trace.module_fn("jit_evo_seed_step(3)") == "evo_seed_step"
    assert trace.op_name("%while.52 = (s32[]{:T(128)}, f32[33]) while(x)") == "while.52"
    assert trace.op_name("fusion.3") == "fusion.3"


def _dev(name, a, b, line="XLA Ops", dev="/device:TPU:0"):
    return Event(name=name, start=a, end=b, device=dev, line=line)


def _host(name, a, b):
    return Event(name=name, start=a, end=b, line="python")


def test_reduce_synthetic_events():
    events = [
        _host(trace.WINDOW, 0.0, 10.0),
        _host("bench:LPEngine.refine", 1.0, 4.0),
        _host("bench:multilevel.repair_balance", 6.0, 9.5),
        _host("inner", 6.5, 7.5),
        _dev("jit__lp_sweep(1)", 1.0, 3.0, line="XLA Modules"),
        _dev("sort.1", 1.0, 2.0), _dev("fusion.2", 1.5, 3.0),
        _dev("jit_contract_device(2)", 4.0, 6.0, line="XLA Modules"),
        _dev("fusion.3", 4.0, 6.0),
        _dev("jit__lp_sweep(1)", 11.0, 12.0, line="XLA Modules"),  # outside
        _dev("fusion.9", 11.0, 12.0),
    ]
    r = trace.reduce_events(events)
    assert r.window_s == pytest.approx(10.0)
    assert r.busy_s == pytest.approx(4.0)
    assert r.idle_share == pytest.approx(0.6)
    assert r.device_s(["_lp_sweep"]) == pytest.approx(2.0)
    assert r.device_s(["contract_device", "_lp_sweep"]) == pytest.approx(4.0)
    assert r.op_s["_lp_sweep/sort.1"] == pytest.approx(1.0)
    assert r.op_s["_lp_sweep/fusion.2"] == pytest.approx(1.5)
    # gaps: [0,1] in no span, [3,4] in refine, [6,10] (middle 8) in
    # repair_balance, whose child "inner" has ended by then
    assert r.idle["bench:LPEngine.refine"] == pytest.approx(1.0)
    assert r.idle["bench:multilevel.repair_balance"] == pytest.approx(4.0)
    assert r.idle["host"] == pytest.approx(1.0)
    assert r.gap_count == 3
    assert trace.top(r.idle, 1) == [["bench:multilevel.repair_balance",
                                     pytest.approx(4.0)]]


def test_nested_ops_count_self_time():
    events = [_host(trace.WINDOW, 0.0, 10.0),
              _dev("jit__lp_sweep(1)", 0.0, 8.0, line="XLA Modules"),
              _dev("%while.5 = (s32[]) while(x)", 0.0, 8.0),
              _dev("%while.6 = (s32[]) while(y)", 1.0, 7.0),
              _dev("fusion.1", 2.0, 3.0), _dev("sort.2", 4.0, 6.0)]
    r = trace.reduce_events(events)
    assert r.op_s == pytest.approx({"_lp_sweep/while.5": 2.0,
                                    "_lp_sweep/while.6": 3.0,
                                    "_lp_sweep/fusion.1": 1.0,
                                    "_lp_sweep/sort.2": 2.0})
    assert r.busy_s == pytest.approx(8.0)


def test_reduce_two_devices_average():
    events = [_host(trace.WINDOW, 0.0, 4.0),
              _dev("a", 0.0, 2.0, dev="/device:TPU:0"),
              _dev("b", 0.0, 4.0, dev="/device:TPU:1")]
    r = trace.reduce_events(events)
    assert r.devices == 2 and r.busy_s == pytest.approx(3.0)
    assert r.gap_count == 0


def test_reduce_without_window_or_ops_is_none():
    assert trace.reduce_events([_dev("a", 0, 1)]) is None
    assert trace.reduce_events([_host(trace.WINDOW, 0, 1)]) is None


def test_read_small_cpu_trace(tmp_path):
    """One trace recorded on the CPU: its operations carry hlo_module stats
    on host threads, and the reduction finds the jitted function by name."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _lp_sweep(x):
        return jnp.sort(x * 2.0 + 1.0)

    x = jnp.arange(1 << 16, dtype=jnp.float32)
    _lp_sweep(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(3):
            _lp_sweep(x).block_until_ready()
    jax.profiler.stop_trace()
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    r = trace.reduce_file(str(files[-1]))
    assert r is not None
    assert 0 < r.busy_s <= r.window_s
    assert r.device_s(["_lp_sweep"]) > 0


def test_peaks_table():
    p = harness.peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
    assert "cloud.google.com" in json.loads(
        (harness.BENCH / "peaks.json").read_text())["source"]


def test_every_layer_reader_reads_nothing_without_a_trace():
    bench = harness.spec()
    ctx = harness.LayerContext(reduced=None, units=1, compiles=3)
    for m in bench["per_layer"]:
        mod = harness.load_layer(m["name"])
        assert mod.SOURCE == m["source"], m["name"]
        v = mod.read(ctx)
        assert v is None or m["source"] == "program_counter", m["name"]


def test_sweep_roofline_arithmetic():
    mod = harness.load_layer("sweep_roofline.offline")
    sweeps = [dict(n=100, m=1000, iters=3), dict(n=10, m=50, iters=6)]
    assert mod.sweep_bytes(sweeps) == 3 * 12 * 1100 + 6 * 12 * 60
    red = trace.Reduced(window_s=1.0, busy_s=0.5, devices=1,
                        module_s={"_lp_sweep": 1e-6})
    ctx = harness.LayerContext(reduced=red, units=1, compiles=0, sweeps=sweeps,
                               peaks={"hbm_bytes_per_s": 819e9})
    want = 100.0 * mod.sweep_bytes(sweeps) / 819e9 / 1e-6
    assert mod.read(ctx) == pytest.approx(want)


def test_sweep_bytes_ignore_bucket_padding():
    """The roofline's bytes come from each sweep's real n, m and iterations:
    the same sweeps packed into different chunk buckets count the same."""
    from repro.core import LPEngine
    from repro.graph import rmat

    g = rmat(9, 8, seed=3)
    counted, shapes = [], []
    for chunks in (1, 8):
        eng = LPEngine(g, target_chunks=chunks, seed=0)
        sweeps: list = []
        with harness.instrument(sweeps):
            lab = eng.cluster(g, U=8.0, iters=2, seed=1)
            eng.refine(g, np.asarray(lab) % 4, 4, g.n / 3.0, 3, 2)
        shapes.append(eng._pack(g, "random").shape)
        counted.append(harness.load_layer("sweep_roofline.offline").sweep_bytes(sweeps))
        assert [s["iters"] for s in sweeps] == [2, 3]
    assert shapes[0] != shapes[1]
    assert counted[0] == counted[1] == 5 * 12 * (g.n + g.m)


@pytest.mark.parametrize("attr", ["_pack", "refine"])
def test_instrument_fails_when_a_marked_name_is_gone(monkeypatch, attr):
    from repro.core import engine

    monkeypatch.delattr(engine.LPEngine, attr)
    with pytest.raises(harness.Unmarked, match=attr):
        with harness.instrument([]):
            pass
    assert not any(hasattr(getattr(engine.LPEngine, a, None), "__wrapped__")
                   for a in ("cluster", "refine", "contract", "_pack"))


def test_instrument_fails_when_a_sweep_argument_is_gone(monkeypatch):
    from repro.core import engine

    def cluster(self, graph, U, iters, seed, restrict=None):
        raise AssertionError("never called")

    monkeypatch.setattr(engine.LPEngine, "cluster", cluster)
    with pytest.raises(harness.Unmarked, match="'g'"):
        with harness.instrument([]):
            pass
    assert engine.LPEngine.cluster is cluster      # nothing left wrapped
