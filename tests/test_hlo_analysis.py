"""Trip-count-aware HLO analyzer on a hand-built module + a real lowering."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import analyze_hlo


def test_scan_flops_multiplied_by_trip_count():
    """A matmul inside lax.scan must be counted once per iteration."""
    W = jnp.ones((64, 64), jnp.float32)

    def step(x, _):
        return x @ W, None

    def f(x):
        y, _ = jax.lax.scan(step, x, None, length=10)
        return y

    txt = jax.jit(f).lower(jnp.ones((64, 64))).compile().as_text()
    c = analyze_hlo(txt)
    expected = 10 * 2 * 64 * 64 * 64  # 10 iterations x 2*M*N*K
    assert 0.9 * expected <= c.flops <= 1.3 * expected, (c.flops, expected)
    assert c.unknown_trip_loops == 0


def test_unrolled_matches_scan():
    W = jnp.ones((32, 32), jnp.float32)

    def f_unrolled(x):
        for _ in range(6):
            x = x @ W
        return x

    def f_scan(x):
        y, _ = jax.lax.scan(lambda c, _: (c @ W, None), x, None, length=6)
        return y

    t1 = jax.jit(f_unrolled).lower(jnp.ones((32, 32))).compile().as_text()
    t2 = jax.jit(f_scan).lower(jnp.ones((32, 32))).compile().as_text()
    f1, f2 = analyze_hlo(t1).flops, analyze_hlo(t2).flops
    assert abs(f1 - f2) / max(f1, f2) < 0.05, (f1, f2)


def test_collectives_counted():
    import os
    import subprocess
    import sys

    code = """
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.hlo_analysis import analyze_hlo
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("x",))
sh = NamedSharding(mesh, P("x", None))
f = jax.jit(lambda a: (a @ a.T).sum(), in_shardings=sh)
txt = f.lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile().as_text()
c = analyze_hlo(txt)
assert c.collective_total > 0, c.collective_bytes
print("COLL-OK", c.collective_bytes)
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert "COLL-OK" in r.stdout, r.stderr[-2000:]
