"""The reduction of the program's own spans (``bench/spans.py``): synthetic
events, and a small partition() traced on the CPU beside the harness's
``instrument``."""

import json

import pytest

from bench import harness, spans, trace
from bench.spans import ArgEvent

SIX = ("idle_share.offline", "sweep_s.offline", "sweep_roofline.offline",
       "evo_s.offline", "contract_s.offline", "window_compiles.offline")


def _span(name, a, b, line="python", **args):
    return ArgEvent(name=name, start=a, end=b, line=line,
                    args={"cat": name.split(".")[0], **args})


def _host(name, a, b):
    return ArgEvent(name=name, start=a, end=b, line="python")


def _dev(name, a, b, line="XLA Ops"):
    return ArgEvent(name=name, start=a, end=b, device="/device:TPU:0", line=line)


def _synthetic():
    return [
        _host(trace.WINDOW, 0.0, 10.0),
        _span("partition", 0.5, 9.2, host_reads=5, evo_grow_rounds=7, seed=2),
        _span("vcycle.pack", 1.0, 3.0, n=100, mode="degree"),
        _host("bench:LPEngine._pack", 0.9, 3.2),      # not a program span
        _span("host.read", 2.0, 2.5, bytes=64, what="cut"),
        _span("vcycle.pack", 11.0, 12.0, n=100),      # outside the window
        _span("py.gc", 4.0, 4.5, line="other", generation=2, collected=3),
        _dev("jit__lp_sweep(1)", 3.0, 6.0, line="XLA Modules"),
        _dev("fusion.1", 3.0, 6.0),
        _dev("jit_evo_seed_step(2)", 7.0, 9.0, line="XLA Modules"),
        _dev("fusion.2", 7.0, 9.0),
    ]


def test_reduce_spans_self_time_args_and_idle_by_span():
    red = spans.reduce_spans(_synthetic())
    assert red.window_s == pytest.approx(10.0)
    assert red.idle_s == pytest.approx(5.0)           # [0,3] [6,7] [9,10]
    assert set(red.spans) == {"partition", "vcycle.pack", "host.read", "py.gc"}
    pack = red.spans["vcycle.pack"]
    assert pack.count == 1 and pack.total_s == pytest.approx(2.0)
    assert pack.self_s == pytest.approx(1.5)          # its host.read is a child
    assert pack.args == {"n": 100}                    # strings are not summed
    part = red.spans["partition"]
    assert part.self_s == pytest.approx(8.7 - 2.0)
    assert part.args == {"host_reads": 5, "evo_grow_rounds": 7, "seed": 2}
    assert red.spans["py.gc"].self_s == pytest.approx(0.5)  # its own thread
    # gap middles 1.5 (pack), 6.5 (partition, no child), 9.5 (no span);
    # the non-program bench: annotation never names a gap
    assert red.idle_spans == pytest.approx(
        {"vcycle.pack": 3.0, "partition": 1.0, spans.NO_SPAN: 1.0})
    assert spans.below_partition_share(red) == pytest.approx(0.6)


def test_reduce_spans_sums_args_over_spans():
    ev = _synthetic() + [_span("partition", 9.6, 9.9, host_reads=4,
                               evo_grow_rounds=1)]
    red = spans.reduce_spans(ev)
    assert red.spans["partition"].count == 2
    assert red.spans["partition"].args["host_reads"] == 9
    assert spans.host_reads(red, 2) == pytest.approx(4.5)
    assert spans.evo_grow_rounds(red, 2) == pytest.approx(4.0)


def test_span_readers_on_a_synthetic_reduction():
    red = spans.reduce_spans(_synthetic())
    assert spans.host_pack_s(red, 2) == pytest.approx(0.75)
    assert spans.host_reads(red, 2) == pytest.approx(2.5)
    assert spans.evo_grow_rounds(red, 2) == pytest.approx(3.5)


@pytest.mark.parametrize("reader", [spans.host_pack_s, spans.host_reads,
                                    spans.evo_grow_rounds])
def test_span_readers_read_nothing_without_spans(reader):
    assert reader(None, 3) is None
    bare = spans.reduce_spans([_host(trace.WINDOW, 0.0, 2.0),
                               _dev("fusion.1", 0.5, 1.0)])
    assert bare is not None and bare.spans == {}
    assert reader(bare, 3) is None
    assert reader(spans.reduce_spans(_synthetic()), 0) is None


def test_reduce_spans_without_window_or_ops_is_none():
    assert spans.reduce_spans([_span("partition", 0, 1)]) is None
    assert spans.reduce_spans([_host(trace.WINDOW, 0, 1)]) is None


# ------------------------------------------------ a partition traced on the CPU


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """One engine-path partition() (with a GA generation, so every jitted
    function the layer readers name runs) under the profiler, the harness's
    ``instrument`` and the program's spans together."""
    import jax
    from repro.core import PartitionerConfig, partition
    from repro.graph import rmat

    g = rmat(10, 8, seed=2)
    cfg = PartitionerConfig(k=4, seed=1, engine="jnp", coarsest_factor=32,
                            generations=1)
    partition(g, cfg)                   # compiles outside the trace
    out = tmp_path_factory.mktemp("xplane")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    sweeps: list = []
    jax.profiler.start_trace(str(out), profiler_options=opts)
    try:
        with harness.instrument(sweeps), jax.profiler.TraceAnnotation(trace.WINDOW):
            rep = partition(g, cfg)
    finally:
        jax.profiler.stop_trace()
    events = spans.read_events(str(sorted(out.rglob("*.xplane.pb"))[-1]))
    return dict(rep=rep, sweeps=sweeps, events=events)


def test_sweep_span_args_equal_what_instrument_records(cpu_trace):
    got = [dict(n=e.args["n"], m=e.args["m"], iters=e.args["iters"])
           for e in sorted(cpu_trace["events"], key=lambda e: e.start)
           if e.is_span and e.name == "vcycle.sweep"]
    assert got and got == cpu_trace["sweeps"]


def test_every_layer_function_runs_as_a_module(cpu_trace):
    red = trace.reduce_events(cpu_trace["events"])
    assert red is not None
    for path in sorted((harness.BENCH / "layers").glob("*.py")):
        mod = harness.load_layer(path.stem)
        for fn in getattr(mod, "FUNCTIONS", ()):
            assert red.module_s.get(fn, 0) > 0, (path.name, fn)


def test_cpu_trace_spans_and_counters(cpu_trace):
    red = spans.reduce_spans(cpu_trace["events"])
    stats = cpu_trace["rep"].engine_stats
    assert {"partition", "vcycle.level", "vcycle.pack", "vcycle.sweep",
            "vcycle.contract", "vcycle.evolve", "vcycle.uncoarsen",
            "vcycle.project", "partition.finalize", "host.read"} <= set(red.spans)
    assert spans.host_reads(red, 1) == stats["host_reads"] > 0
    assert spans.evo_grow_rounds(red, 1) == stats["evo_grow_rounds"] > 0
    assert 0 < spans.host_pack_s(red, 1) <= red.spans["vcycle.pack"].total_s
    assert sum(red.idle_spans.values()) == pytest.approx(red.idle_s)


def test_six_metrics_read_the_same_with_program_spans_in_the_trace(cpu_trace):
    """The program's annotations are host events on the window's thread:
    they rename idle gaps but move none of the six accepted metrics."""
    events = cpu_trace["events"]
    bare = [e for e in events if not e.is_span]
    peaks = harness.peaks_for("TPU v5 lite")
    values = []
    for evs in (events, bare):
        red = trace.reduce_events(evs)
        ctx = harness.LayerContext(reduced=red, units=1, compiles=0,
                                   sweeps=cpu_trace["sweeps"], peaks=peaks)
        values.append({m: harness.load_layer(m).read(ctx) for m in SIX})
        assert sum(red.idle.values()) == pytest.approx(
            red.window_s - red.busy_s, rel=1e-6, abs=1e-6)
    assert values[0] == values[1]
    assert all(values[0][m] is not None for m in SIX)
    names = {m["name"] for m in json.loads(
        (harness.ROOT / "BENCHMARK.json").read_text())["per_layer"]}
    assert set(SIX) <= names
