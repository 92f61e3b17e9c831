"""Subprocess helper for bench_serving's mesh-scaling harness: forces 8
fake host devices, then drives sustained multi-client traffic against a
MeshServer at each mesh size.  Prints ``ROW,...`` CSV lines to stdout.

Per mesh size p ∈ {1, 2, 4, 8}:
  * ``foldin_bulk`` — steady-state sharded ``project()`` of a full bucket,
    p50/p99 latency and rows/s (device-parallel throughput);
  * ``topk`` — sharded retrieval (per-shard scan + log-p candidate merge),
    p50/p99 and queries/s;
  * ``sustained`` — the open-loop multi-client harness: C client threads
    each submit single-row requests on a FIXED arrival schedule
    (independent of completion — the open-loop discipline that surfaces
    queueing delay, unlike closed-loop clients that self-throttle), through
    the MicroBatcher; per-request latency is measured from the scheduled
    arrival, so p99 includes coalescing + queueing under load.
"""

import sys

from repro.util import env

env.configure(host_device_count=8, platform="cpu")   # before any jax import

import threading  # noqa: E402
import time  # noqa: E402

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.serve.artifact import FactorArtifact  # noqa: E402
from repro.serve.mesh import MeshServer, serve_mesh  # noqa: E402

M, N, K = 4096, 256, 12
MAX_BATCH = 64
REPS = 20
CLIENTS = 8
REQ_PER_CLIENT = 30
ARRIVAL_S = 5e-3          # per-client inter-arrival (open-loop schedule)


def _pcts(samples_s):
    return (float(np.percentile(samples_s, 50) * 1e6),
            float(np.percentile(samples_s, 99) * 1e6))


def _bench(fn, arg, reps=REPS):
    jax.block_until_ready(fn(arg))
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(arg))
        times.append(time.perf_counter() - t0)
    return _pcts(times)


def main():
    sizes = [int(s) for s in (sys.argv[1:] or ["1", "2", "4", "8"])]
    rng = np.random.RandomState(5)
    W = rng.rand(M, K).astype(np.float32) + 0.05
    H = rng.rand(K, N).astype(np.float32) + 0.05
    art = FactorArtifact.from_factors(W, H, algo="bpp")
    batch = jnp.asarray(rng.rand(MAX_BATCH, N).astype(np.float32))
    queries = jnp.asarray(rng.rand(16, K).astype(np.float32))
    reqs = rng.rand(CLIENTS * REQ_PER_CLIENT, N).astype(np.float32)

    for p in sizes:
        srv = MeshServer(art, mesh=serve_mesh(p), max_batch=MAX_BATCH,
                         chunk=1024, metric="cosine", max_delay_s=2e-3)
        with srv:
            p50, p99 = _bench(srv.project, batch)
            print(f"ROW,foldin_bulk,{p},{p50:.1f},{p99:.1f},"
                  f"{MAX_BATCH / (p50 / 1e6):.1f}", flush=True)

            p50, p99 = _bench(lambda q: srv.query(q, k=10)[0], queries)
            print(f"ROW,topk,{p},{p50:.1f},{p99:.1f},"
                  f"{16 / (p50 / 1e6):.1f}", flush=True)

            lat = np.zeros(len(reqs))
            t_base = time.perf_counter() + 0.05

            def client(c):
                for j in range(REQ_PER_CLIENT):
                    i = c * REQ_PER_CLIENT + j
                    sched = t_base + j * ARRIVAL_S
                    now = time.perf_counter()
                    if sched > now:
                        time.sleep(sched - now)
                    srv.submit(reqs[i]).result(timeout=120)
                    lat[i] = time.perf_counter() - sched
            threads = [threading.Thread(target=client, args=(c,))
                       for c in range(CLIENTS)]
            t_all = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t_all
            p50, p99 = _pcts(lat)
            print(f"ROW,sustained,{p},{p50:.1f},{p99:.1f},"
                  f"{len(reqs) / wall:.1f}", flush=True)


if __name__ == "__main__":
    main()
