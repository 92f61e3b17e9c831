"""Multi-device streaming checks, run in ONE subprocess with 8 fake host
devices (tests/test_online.py drives this).  Prints "PASS <name>" per
check; exits nonzero on any failure.

Covers the acceptance criteria of the online train→serve loop on a mesh:
  * streaming ingest over ``serve_mesh(p)`` — fold-in warm starts, drift
    decisions, and versioned publishes all work against a sharded serving
    path, with sparse (BCOO) ingest matching dense bit-for-bit;
  * HLO wire-format: the batch-sharded fold-in the ingest path uses moves
    NOTHING between devices — ingested A-rows (and client request rows)
    never cross the wire, before OR after the lineage has evolved;
  * re-shard-on-swap: an artifact trained on 1 device is consumed by a
    4-device ``MeshServer``, and hot-swapping its evolved successors under
    traffic never retraces (compile-count flatness — the module-wide
    shared jit cache contract);
  * the version stamp survives the mesh: responses served mid-publish
    match an independent cold projection at their stamped version.
"""

from repro.util import env

env.configure(host_device_count=8, platform="cpu")   # before any jax import

import os
import sys
import threading
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from repro.core.engine import NMFSolver
from repro.data.pipeline import stream_batch
from repro.online import OnlineNMF
from repro.roofline.hlo import collective_stats
from repro.serve.artifact import FactorArtifact
from repro.serve.foldin import FoldInProjector
from repro.serve.mesh import MeshServer, serve_mesh

FAILURES = []


def check(name):
    def deco(fn):
        try:
            fn()
            print(f"PASS {name}", flush=True)
        except Exception:
            FAILURES.append(name)
            print(f"FAIL {name}", flush=True)
            traceback.print_exc()
    return deco


SEED = int(os.environ.get("REPRO_TEST_SEED", "20260808"))
N, K = 64, 6
A0 = np.asarray(stream_batch(SEED, 0, rows=48, n=N, k=K, noise=0.01))
MESH8 = serve_mesh(8)

assert len(jax.devices()) == 8, "forced host device count did not apply"


@check("streaming_ingest_over_mesh")
def _():
    with OnlineNMF(A0, k=K, algo="bpp", key=jax.random.PRNGKey(SEED),
                   mesh=MESH8, n_blocks=8, block_threshold=0.02,
                   full_threshold=np.inf) as svc:
        err0 = svc.rel_err()
        actions = []
        for step in range(1, 6):
            rep = svc.ingest(stream_batch(SEED, step, rows=16, n=N, k=K,
                                          drift=0.5))
            actions.append(rep.action)
            assert rep.version == step
        assert "refresh" in actions, actions
        assert np.isfinite(svc.rel_err()) and svc.shape == (48 + 5 * 16, N)
        scores, idx, v = svc.retrieve(A0[:3], k=5)
        assert v == 5 and np.asarray(idx).shape == (3, 5)
        assert err0 < 0.2


@check("sparse_ingest_matches_dense_on_mesh")
def _():
    rng = np.random.RandomState(SEED % (2 ** 31))
    dense = (rng.rand(16, N) * (rng.rand(16, N) < 0.2)).astype(np.float32)
    res = NMFSolver(K, algo="bpp", max_iters=120, tol=1e-5) \
        .fit(jnp.asarray(A0), key=jax.random.PRNGKey(SEED))
    mk = lambda mesh: OnlineNMF(A0, k=K, algo="bpp", result=res, mesh=mesh,
                                block_threshold=np.inf,
                                full_threshold=np.inf)
    with mk(MESH8) as sp, mk(MESH8) as dn, mk(None) as local:
        sp.ingest(jsparse.BCOO.fromdense(jnp.asarray(dense)))
        dn.ingest(dense)
        local.ingest(dense)
        np.testing.assert_allclose(sp.W, dn.W, atol=1e-6)
        # and the mesh path agrees with the single-device path
        np.testing.assert_allclose(dn.W, local.W, atol=1e-4)


@check("hlo_ingest_foldin_moves_no_rows")
def _():
    """The ingest warm start and every client request use the
    batch-sharded fold-in: its compiled HLO must contain NO collectives —
    ingested A-rows never cross the wire.  Holds for the root artifact and
    after the lineage has evolved through refreshes."""
    with OnlineNMF(A0, k=K, algo="bpp", key=jax.random.PRNGKey(SEED),
                   mesh=MESH8, n_blocks=8, block_threshold=0.05,
                   full_threshold=np.inf) as svc:
        for bucket in (8, 32):
            hlo = svc._projector.lower_dense(bucket).compile().as_text()
            stats = collective_stats(hlo)
            assert not stats.counts, \
                f"root fold-in has collectives:\n{stats.table()}"
        for step in range(1, 4):
            svc.ingest(stream_batch(SEED, step, rows=16, n=N, k=K,
                                    drift=0.3))
        assert svc.version == 3
        hlo = svc._projector.lower_dense(16).compile().as_text()
        stats = collective_stats(hlo)
        assert not stats.counts, \
            f"evolved fold-in has collectives:\n{stats.table()}"


@check("reshard_on_swap_no_retrace")
def _():
    """1-device-trained artifact → 4-device MeshServer; hot-swapping its
    evolved successors must not retrace (compile_count flat after the
    first warmup — the shared jit-cache contract)."""
    mesh4 = serve_mesh(4)
    res = NMFSolver(K, algo="bpp", max_iters=120, tol=1e-5) \
        .fit(jnp.asarray(A0), key=jax.random.PRNGKey(SEED))
    art = FactorArtifact.from_result(res)          # unsharded, as trained
    rng = np.random.RandomState(SEED % (2 ** 31) + 1)
    with MeshServer(art, mesh=mesh4, warmup=True) as srv:
        for b in (1, 3, 8, 17):
            srv.project(rng.rand(b, N).astype(np.float32))
        warm = srv.projector.compile_count
        cur = art
        for _ in range(3):                          # H-refresh swaps
            cur = cur.evolve(H=np.asarray(cur.H) * 0.9)
            srv.swap(cur)
            for b in (1, 3, 8, 17):
                srv.project(rng.rand(b, N).astype(np.float32))
        assert srv.version == 3
        assert srv.projector.compile_count == warm, \
            f"swap retraced: {srv.projector.compile_count} != {warm}"
        scores, idx = srv.retrieve(A0[:2], k=4)
        assert np.asarray(idx).shape == (2, 4)


@check("version_stamps_consistent_on_mesh")
def _():
    probes = np.asarray(stream_batch(SEED, 9, rows=2, n=N, k=K), np.float32)
    arts, results, errors = {}, [], []
    stop = threading.Event()
    lock = threading.Lock()
    with OnlineNMF(A0, k=K, algo="bpp", key=jax.random.PRNGKey(SEED),
                   mesh=MESH8, n_blocks=8, block_threshold=0.05,
                   full_threshold=np.inf, max_delay_s=1e-4) as svc:
        arts[0] = svc.artifact

        def client(tid):
            try:
                futs = []
                while not stop.is_set():
                    futs.append((tid, svc.submit(probes[tid])))
                    time.sleep(0.002)
                for tid_, f in futs:
                    with lock:
                        results.append((tid_, f.result(timeout=60)))
            except Exception as e:
                errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(2)]
        for t in threads:
            t.start()
        for step in range(1, 5):
            rep = svc.ingest(stream_batch(SEED, step, rows=12, n=N, k=K,
                                          drift=0.4))
            arts[rep.version] = svc.artifact
        stop.set()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
    assert results
    expected = {}
    for v, art in arts.items():
        codes = np.asarray(FoldInProjector(art, mesh=MESH8).project(
            jnp.asarray(probes)))
        for tid in range(2):
            expected[(tid, v)] = codes[tid]
    for tid, r in results:
        assert r.version in arts, r.version
        assert np.allclose(np.asarray(r.code), expected[(tid, r.version)],
                           atol=1e-5), \
            f"response stamped v{r.version} does not match that version"


print(f"{len(FAILURES)} failures", flush=True)
sys.exit(1 if FAILURES else 0)
