import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the PAPER's own distributed SCLaP sweep at web scale.

Lowers + compiles one coarsening sweep (3 LP phases over chunked local
nodes + interface all_gather exchange) and one refinement sweep (psum block
weights, k=16) for a uk-2007-scale graph — n = 105.8M nodes, m = 3.3G arcs
— sharded over the production meshes.  This is the scale the paper
partitions in 15.2 s on 512 cores; the dry-run proves the shard_map
formulation lowers, compiles and fits on a 256/512-chip pod.

  python -m repro.launch.dryrun_paper [--mesh single|multi]
"""

import argparse
import functools
import gzip
import json
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--n", type=float, default=105.8e6)
    ap.add_argument("--m", type=float, default=3.3e9)   # undirected edges
    ap.add_argument("--k", type=int, default=16)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..core.distributed_lp import _shard_sweep
    from .hlo_analysis import analyze_hlo
    from .roofline import HW

    multi = args.mesh == "multi"
    n_chips = 512 if multi else 256
    # flatten the production mesh into the paper's 1-D PE ring
    devs = np.array(jax.devices()[:n_chips])
    mesh = jax.sharding.Mesh(devs, ("pe",))

    Pn = n_chips
    n = int(args.n)
    arcs = int(2 * args.m)
    maxN = -(-n // Pn)
    maxM = -(-arcs // Pn)
    ghost_frac = 0.10          # paper: <0.5% (rgg) .. 40% (del); web ~10%
    maxG = int(maxN * ghost_frac) // 8 * 8 + 8
    maxI = maxG
    C = 4                       # chunks per shard
    Nc = -(-maxN // C) // 8 * 8 + 8
    Ec = -(-maxM // C) // 8 * 8 + 8

    S = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    abstract = dict(
        ch_nodes=S((Pn, C, Nc), i32), ch_nv=S((Pn, C, Nc), jnp.bool_),
        ch_ed=S((Pn, C, Ec), i32), ch_ew=S((Pn, C, Ec), f32),
        ch_es=S((Pn, C, Ec), i32), ch_ev=S((Pn, C, Ec), jnp.bool_),
        nw=S((Pn, maxN), f32), gnw=S((Pn, maxG), f32),
        gow=S((Pn, maxG), i32), gsl=S((Pn, maxG), i32),
        ifn=S((Pn, maxI), i32), nloc=S((Pn,), i32), ngho=S((Pn,), i32),
        ll=S((Pn, maxN), i32), lg=S((Pn, maxG), i32),
    )
    spec = P("pe")
    shardings = {k: NamedSharding(mesh, spec if v.shape[0] == Pn else P())
                 for k, v in abstract.items()}

    rec_all = {}
    for mode, iters, kk in (("cluster", 3, 0), ("refine", 6, args.k)):
        def body(ch_nodes, ch_nv, ch_ed, ch_ew, ch_es, ch_ev, nw, gnw, gow,
                 gsl, ifn, nloc, ngho, ll_, lg_, key,
                 _mode=mode, _iters=iters, _k=kk):
            out = _shard_sweep(
                ch_nodes[0], ch_nv[0], ch_ed[0], ch_ew[0], ch_es[0], ch_ev[0],
                nw[0], gnw[0], gow[0], gsl[0], ifn[0], nloc[0], ngho[0],
                ll_[0], lg_[0], jnp.float32(1e6), key,
                iters=_iters, refine_mode=(_mode == "refine"), k=_k,
                maxN=maxN, maxG=maxG, maxI=maxI,
            )
            return out[0][None], out[1][None], out[2]

        shmapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(spec,) * 15 + (P(),),
            out_specs=(spec, spec, P()),
            check_vma=False,
        )
        jitted = jax.jit(
            shmapped,
            in_shardings=tuple(shardings.values()) + (NamedSharding(mesh, P()),),
            donate_argnums=(13, 14),
        )
        t0 = time.time()
        lowered = jitted.lower(*abstract.values(), S((2,), jnp.uint32))
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
        mem = compiled.memory_analysis()
        txt = compiled.as_text()
        hc = analyze_hlo(txt)
        bytes_dev = int(mem.argument_size_in_bytes + mem.output_size_in_bytes
                        - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
        terms = {
            "compute_s": hc.flops / HW["peak_flops"],
            "memory_s": hc.hbm_bytes / HW["hbm_bw"],
            "collective_s": hc.collective_total / HW["link_bw"],
        }
        rec = {
            "arch": "paper-sclap", "shape": f"uk2007_{mode}", "mesh": args.mesh,
            "variant": "base", "kind": mode, "n_chips": n_chips,
            "status": "ok", "t_lower_s": round(t_lower, 1),
            "t_compile_s": round(t_compile, 1),
            "bytes_per_device": bytes_dev,
            "gib_per_device": round(bytes_dev / 2**30, 3),
            "graph": {"n": n, "arcs": arcs, "ghost_frac": ghost_frac,
                      "chunks": C},
            "roofline": {
                **terms,
                "dominant": max(terms, key=terms.get),
                "hlo_flops_per_dev": hc.flops,
                "hlo_bytes_per_dev": hc.hbm_bytes,
                "collective_bytes_per_dev": hc.collective_total,
                "collectives": dict(hc.collective_bytes),
                "unknown_trip_loops": hc.unknown_trip_loops,
            },
        }
        path = os.path.join(args.out,
                            f"paper-sclap__uk2007_{mode}__{args.mesh}__base.json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        with gzip.open(path.replace(".json", ".hlo.txt.gz"), "wt") as f:
            f.write(txt)
        print(json.dumps({k: rec[k] for k in
                          ("shape", "mesh", "t_compile_s", "gib_per_device")},
                         indent=None))
        rec_all[mode] = rec
    return rec_all


if __name__ == "__main__":
    main()
