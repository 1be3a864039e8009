"""``dstpu_bench`` — collective micro-benchmark CLI.

Reference: ``bin/ds_bench`` (the comm benchmark entry; the sweep suites live
in DeepSpeedExamples, benchmarks/README.md:4-6). TPU-native version: build a
mesh over the available chips, run each collective (psum / all_gather /
reduce_scatter / all_to_all / ppermute) across a message-size sweep inside
``shard_map``, and report alg-bandwidth and bus-bandwidth per size
(utils/comms_logging.py's accounting).

Size convention (nccl-tests style): ``--sizes-mb`` is the PER-DEVICE local
buffer; algbw = local_bytes / time. Bus-bandwidth factors over N devices:
allreduce 2(N-1)/N, allgather (N-1) (each device receives the other N-1
shards), reducescatter (N-1)/N, alltoall (N-1)/N, ppermute 1.
"""

import argparse
import json
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _mesh(axis: str):
    from deepspeed_tpu import comm

    if not comm.is_initialized():
        comm.init_distributed(mesh_shape={axis: -1}, verbose=False)
    return comm.get_mesh()


def _timed(fn, x, iters: int) -> float:
    out = fn(x)  # compile
    _ = float(jnp.sum(out.astype(jnp.float32)))  # host fetch: waits for the device
    t0 = time.time()
    for _i in range(iters):
        out = fn(x)
    _ = float(jnp.sum(out.astype(jnp.float32)))
    return (time.time() - t0) / iters


def collective_fns(mesh, axis: str):
    from jax.sharding import PartitionSpec as P

    n = mesh.shape[axis]
    sm = partial(jax.shard_map, mesh=mesh, check_vma=False)

    fns = {
        # x sharded over axis; result replicated-summed
        "all_reduce": (
            sm(lambda x: jax.lax.psum(x, axis), in_specs=P(axis), out_specs=P(axis)),
            2.0 * (n - 1) / n,
        ),
        "all_gather": (
            sm(lambda x: jax.lax.all_gather(x, axis, tiled=True), in_specs=P(axis), out_specs=P()),
            float(n - 1),
        ),
        "reduce_scatter": (
            sm(lambda x: jax.lax.psum_scatter(x, axis, tiled=True), in_specs=P(axis), out_specs=P(axis)),
            float(n - 1) / n,
        ),
        "all_to_all": (
            sm(lambda x: jax.lax.all_to_all(x.reshape(n, -1), axis, 0, 0, tiled=False).reshape(x.shape),
               in_specs=P(axis), out_specs=P(axis)),
            float(n - 1) / n,
        ),
        "ppermute": (
            sm(lambda x: jax.lax.ppermute(x, axis, [(i, (i + 1) % n) for i in range(n)]),
               in_specs=P(axis), out_specs=P(axis)),
            1.0,
        ),
    }
    return fns


def run(sizes_mb, iters: int, axis: str, dtype=jnp.bfloat16, ops=None):
    from deepspeed_tpu.comm.comms_logging import convert_size

    mesh = _mesh(axis)
    n = mesh.shape[axis]
    results = []
    for name, (fn, bus_factor) in collective_fns(mesh, axis).items():
        if ops and name not in ops:
            continue
        for mb in sizes_mb:
            # per-DEVICE buffer of mb MiB: global array is n shards of it
            local_bytes = int(mb * 1024 * 1024)
            elems = max(n, local_bytes // jnp.dtype(dtype).itemsize * n)
            x = jax.device_put(
                jnp.ones((elems,), dtype),
                jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(axis)),
            )
            try:
                dt = _timed(fn, x, iters)
            except Exception as e:
                results.append({"op": name, "size": convert_size(local_bytes), "error": str(e)[:120]})
                continue
            nbytes = local_bytes
            algbw = nbytes / dt
            results.append({
                "op": name,
                "size": convert_size(nbytes),
                "time_ms": round(dt * 1e3, 3),
                "algbw_gbps": round(algbw / 1e9, 3),
                "busbw_gbps": round(algbw * bus_factor / 1e9, 3),
            })
    return {"devices": n, "axis": axis, "results": results}


def main(argv=None):
    ap = argparse.ArgumentParser("dstpu_bench", description="collective micro-benchmarks")
    ap.add_argument("--sizes-mb", type=float, nargs="+", default=[1, 8, 64])
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--axis", default="data")
    ap.add_argument("--ops", nargs="*", default=None,
                    help="subset of: all_reduce all_gather reduce_scatter all_to_all ppermute")
    ap.add_argument("--json", action="store_true", help="one JSON document instead of a table")
    args = ap.parse_args(argv)
    report = run(args.sizes_mb, args.iters, args.axis, ops=args.ops)
    if args.json:
        print(json.dumps(report, indent=1))
        return 0
    print(f"devices={report['devices']} axis={report['axis']}")
    print(f"{'op':<16}{'size':>10}{'time':>12}{'algbw':>12}{'busbw':>12}")
    for r in report["results"]:
        if "error" in r:
            print(f"{r['op']:<16}{r['size']:>10}  ERROR {r['error']}")
        else:
            print(f"{r['op']:<16}{r['size']:>10}{r['time_ms']:>10.3f}ms"
                  f"{r['algbw_gbps']:>10.2f}GB{r['busbw_gbps']:>10.2f}GB")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
