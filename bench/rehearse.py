#!/usr/bin/env python3
"""Compile the cells' device programs for a described TPU v5e, without one.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload products.ingest

Compiles, for one chip of a described ``v5e:2x2``, the programs a cell
drives at its largest shapes: the argkmin pass over the store's capacity
rung, the ``ref`` solve at the unlabelled-row rung, the fused read
gather, and the benchmark's own preload and reference passes.  The TPU
compiler then refuses here what it would refuse on the chip (VMEM, size,
layout), and prints each program's memory.  Nothing runs.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import cells
    import lp
    import preload
    import reference
    from repro.core.propagate import PropagationProblem
    from repro.core.snapshot import _device_query, bucket, bucket_k
    from repro.ingest.embedding_store import batch_bucket, cap_bucket
    from repro.kernels import argkmin, ops

    cell = cells.cell(args.workload)
    cfg, mix = cell["config"], cell["mix"]
    n0, d, k = int(cfg["rows"]), int(cfg["emb_dim"]), int(cfg["k"])
    dp = lp.dim_pad(d)
    cap = cap_bucket(n0 + int(mix["insert_pool_rows"]))
    m = batch_bucket(int(mix["service"]["window_ops"]))
    u = bucket(n0 - int(cfg["labelled"]))
    kk = bucket_k(4 * k)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    f32, i32 = jnp.float32, jnp.int32
    progs = {
        f"argkmin pallas C={cap} M={m} D={dp}": (
            argkmin._argkmin_pallas,
            (sds((cap, dp), f32), sds((cap,), bool), sds((cap,), f32), sds((m, dp), f32),
             sds((m,), bool), sds((), i32), sds((), f32), sds((), i32)),
            dict(topk=k + lp.SELECT_MARGIN, block_rows=256, interpret=False)),
        f"ref solve U={u} K={kk}": (
            ops._ref_donating,
            (PropagationProblem(sds((u, kk), i32), sds((u, kk), f32), sds((u,), f32),
                                sds((u,), f32), sds((u,), bool)),
             sds((u,), f32), sds((u,), bool), sds((), f32)),
            dict(max_iters=200_000)),
        f"read gather N={bucket(cap)}": (
            _device_query,
            (sds((bucket(cap),), f32), sds((bucket(cap),), jnp.int8),
             sds((bucket(cap),), bool), sds((1024,), i32), sds((1024,), f32)), {}),
        f"preload nominate rows={n0}": (
            preload._nominate,
            (sds((-32768 * (-n0 // 32768), dp), f32), sds((-32768 * (-n0 // 32768),), bool),
             sds((), i32)),
            dict(t=k + lp.SELECT_MARGIN, q=preload.NOMINATE_QUERY, ch=preload.NOMINATE_CHUNK,
                 g=preload.NOMINATE_GROUP)),
        f"preload jacobi U={n0 - int(cfg['labelled'])}": (
            preload.jacobi,
            (sds((n0 - int(cfg["labelled"]), 4 * k), i32),
             sds((n0 - int(cfg["labelled"]), 4 * k), f32),
             *[sds((n0 - int(cfg["labelled"]),), f32)] * 3, sds((), f32)),
            dict(max_iters=20000)),
        f"reference window pass C={cap} M={m}": (
            reference._window_pass,
            (sds((cap, dp), f32), sds((cap,), bool), sds((m, dp), f32), sds((m,), bool),
             sds((), i32)),
            dict(t=k + lp.SELECT_MARGIN, ch=preload.NOMINATE_CHUNK,
                 precision=reference.PRECISIONS["highest"])),
    }
    failed = 0
    for name, (fn, shapes, statics) in progs.items():
        t0 = time.perf_counter()
        try:
            c = fn.lower(*shapes, **statics).compile()
            mem = c.memory_analysis()
            gb = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes) / 1e9
            print(f"[compiled] {name}: {time.perf_counter() - t0:.1f} s, "
                  f"args+out+temp {gb:.3f} GB", flush=True)
        except Exception as e:  # noqa: BLE001 — report every refusal
            failed += 1
            print(f"[refused] {name}: {type(e).__name__}: {str(e)[:400]}", flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
