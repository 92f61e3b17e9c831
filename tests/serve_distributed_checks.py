"""Multi-device serving checks, run in ONE subprocess with 8 fake host
devices (tests/test_serve.py drives this).  Prints "PASS <name>" per
check; exits nonzero on any failure.

Covers the acceptance criteria of mesh-sharded serving:
  * sharded fold-in (batch- and feature-sharded, dense and sparse — incl.
    the sorted-SpMM layout that only the mesh path can serve) matches the
    single-device projector, and recovers W rows from exact A rows;
  * sharded top-k (tree merge on power-of-two meshes, gather merge
    otherwise; dot/cosine × latent/Gram) matches single-device scores and
    indices bit-for-bit on tie-free inputs;
  * the no-retrace contract holds on the sharded path: compile_count is
    flat across the bucket ladder after warmup;
  * HLO wire-format: batch-sharded fold-in moves NOTHING between devices,
    feature-sharded fold-in moves only the k-width (B, k) psum, and
    sharded top-k moves only (b, k) candidate sets — W shards and request
    rows never cross the wire;
  * MeshServer serves end-to-end (submit/retrieve) and hot-swaps
    artifacts under live traffic.
"""

from repro.util import env

env.configure(host_device_count=8, platform="cpu")   # before any jax import

import os
import sys
import tempfile
import threading
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import sparse as jsparse

from repro.backends.sparse import SparseOps
from repro.roofline.hlo import collective_dtype_stats, collective_stats
from repro.serve.artifact import FactorArtifact
from repro.serve.foldin import FoldInProjector
from repro.serve.mesh import MeshServer, serve_mesh
from repro.serve.topk import TopK, _pad_rows, _sharded_topk_fn, topk_rows
from repro.util.compat import make_mesh

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


RNG = np.random.RandomState(11)
M, N, K = 400, 72, 6          # m/8 = 50 local rows >> any candidate set
W_TRUE = RNG.rand(M, K).astype(np.float32) + 0.05
H_TRUE = RNG.rand(K, N).astype(np.float32) + 0.05
ART = FactorArtifact.from_factors(W_TRUE, H_TRUE, algo="bpp")
MESH8 = serve_mesh(8)
ROWS = (W_TRUE[:24] @ H_TRUE).astype(np.float32)   # exact A rows


@check("sharded_batch_foldin_matches_single_device_and_recovers_W")
def _():
    ref = FoldInProjector(ART, max_batch=32)
    for shard_art in (False, True):
        art = ART.shard(MESH8) if shard_art else ART
        proj = FoldInProjector(art, max_batch=32, mesh=MESH8)
        for b in (3, 8, 24):          # uneven, exact, multi-shard buckets
            got = np.asarray(proj.project(ROWS[:b]))
            want = np.asarray(ref.project(ROWS[:b]))
            np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)
        # BPP fold-in of exact rows a_i = w_i H recovers w_i
        got = np.asarray(proj.project(ROWS))
        np.testing.assert_allclose(got, W_TRUE[:24], atol=5e-3, rtol=5e-3)


@check("sharded_features_foldin_matches_single_device")
def _():
    # N = 72 is not divisible by 8: exercises the feature-padding path
    ref = FoldInProjector(ART, max_batch=16)
    proj = FoldInProjector(ART, max_batch=16, mesh=MESH8, shard="features")
    for b in (1, 5, 16):
        got = np.asarray(proj.project(ROWS[:b]))
        want = np.asarray(ref.project(ROWS[:b]))
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)


@check("sharded_sparse_foldin_matches_dense_scatter_and_sorted")
def _():
    dense = (RNG.rand(13, N) * (RNG.rand(13, N) < 0.3)).astype(np.float32)
    A = jsparse.BCOO.fromdense(jnp.asarray(dense))
    ref = np.asarray(FoldInProjector(ART, max_batch=16).project(dense))
    for impl in ("scatter", "sorted"):
        proj = FoldInProjector(ART, max_batch=16, mesh=MESH8,
                               backend=SparseOps(spmm_impl=impl))
        got = np.asarray(proj.project(A))
        np.testing.assert_allclose(got, ref, atol=2e-4, rtol=1e-4)


@check("sharded_topk_matches_single_device_all_metrics")
def _():
    Q = RNG.rand(7, K).astype(np.float32)
    for metric in ("dot", "cosine"):
        for gram in (None, np.asarray(ART.gram)):
            want_s, want_i = topk_rows(W_TRUE, Q, k=5, gram=gram,
                                       metric=metric, chunk=32)
            got_s, got_i = topk_rows(W_TRUE, Q, k=5, gram=gram,
                                     metric=metric, chunk=32, mesh=MESH8)
            assert (np.asarray(got_i) == np.asarray(want_i)).all(), \
                f"{metric}/gram={gram is not None}: index mismatch"
            np.testing.assert_allclose(np.asarray(got_s),
                                       np.asarray(want_s),
                                       atol=2e-4, rtol=1e-4)


@check("gather_merge_on_non_power_of_two_mesh")
def _():
    mesh6 = make_mesh((6,), ("serve",), devices=jax.devices()[:6])
    Q = RNG.rand(4, K).astype(np.float32)
    want_s, want_i = topk_rows(W_TRUE, Q, k=5, chunk=32)
    got_s, got_i = topk_rows(W_TRUE, Q, k=5, chunk=32, mesh=mesh6)
    assert (np.asarray(got_i) == np.asarray(want_i)).all()
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               atol=2e-4, rtol=1e-4)
    try:
        topk_rows(W_TRUE, Q, k=5, chunk=32, mesh=mesh6, merge="tree")
    except ValueError as e:
        assert "power-of-two" in str(e)
    else:
        raise AssertionError("tree merge on p=6 should be rejected")


@check("sharded_no_retrace_across_bucket_ladder")
def _():
    proj = FoldInProjector(ART, max_batch=32, mesh=MESH8)
    warm = proj.warmup(dense=True, sparse=True, nnz_per_row=2)
    for b in (1, 2, 7, 8, 9, 20, 32):
        proj.project(RNG.rand(b, N).astype(np.float32))
        dense = (RNG.rand(b, N) * (RNG.rand(b, N) < 0.05)) \
            .astype(np.float32)
        proj.project(jsparse.BCOO.fromdense(jnp.asarray(dense)))
    assert proj.compile_count == warm, \
        f"retraced: {proj.compile_count} != warmed {warm}"
    # sharded top-k: the lru-cached builder gives one compile per config
    tk = TopK(ART.shard(MESH8), metric="cosine", chunk=32, mesh=MESH8)
    tk.query(RNG.rand(4, K).astype(np.float32), k=5)
    fn = _sharded_topk_fn(MESH8, "serve", 8, 5, "cosine", 32, M, "tree")
    before = fn._cache_size()
    for _ in range(3):
        tk.query(RNG.rand(4, K).astype(np.float32), k=5)
    assert fn._cache_size() == before, \
        f"sharded top-k retraced: {fn._cache_size()} != {before}"


@check("hlo_batch_foldin_moves_nothing")
def _():
    proj = FoldInProjector(ART, max_batch=32, mesh=MESH8)
    hlo = proj.lower_dense(16).compile().as_text()
    st = collective_stats(hlo)
    assert not st.counts, f"batch-sharded fold-in has collectives:\n" \
                          f"{st.table()}"


@check("hlo_features_foldin_only_kwidth_psum")
def _():
    proj = FoldInProjector(ART, max_batch=16, mesh=MESH8, shard="features")
    hlo = proj.lower_dense(16).compile().as_text()
    ents = collective_dtype_stats(hlo)
    assert ents, "feature-sharded fold-in must psum the cross-product"
    for op, dt, dims in ents:
        assert op == "all-reduce", (op, dims)
        assert dt == "f32", (dt, dims)
        sz = int(np.prod(dims)) if dims else 1
        assert sz <= 16 * K, \
            f"wire tensor {dims} exceeds the (B, k) panel"   # k-width only


@check("hlo_sharded_topk_moves_only_candidate_sets")
def _():
    b, k, chunk = 7, 5, 32
    for merge, n_cand in (("tree", k), ("gather", 8 * k)):
        fn = _sharded_topk_fn(MESH8, "serve", 8, k, "dot", chunk, M, merge)
        Wp = _pad_rows(jnp.asarray(W_TRUE), 8)
        Wn = jnp.ones((Wp.shape[0],), jnp.float32)
        Q = jnp.asarray(RNG.rand(b, K).astype(np.float32))
        qn = jnp.ones((b,), jnp.float32)
        hlo = fn.lower(Wp, Wn, Q, qn).compile().as_text()
        ents = collective_dtype_stats(hlo)
        assert ents, "sharded top-k must exchange candidates"
        local_m = Wp.shape[0] // 8
        for op, dt, dims in ents:
            sz = int(np.prod(dims)) if dims else 1
            assert sz <= b * n_cand, \
                f"{merge}: wire tensor {op} {dt}{list(dims)} is bigger " \
                f"than the (b, {n_cand}) candidate set"
            assert all(d < local_m for d in dims), \
                f"{merge}: wire tensor {dims} is W-shard-sized " \
                f"(local m = {local_m})"


@check("sharded_artifact_save_load_roundtrip")
def _():
    art = ART.shard(MESH8)
    assert art.shape == (M, N) and art.valid_rows == M
    assert art.W.shape[0] % 8 == 0
    with tempfile.TemporaryDirectory() as td:
        path = art.save(os.path.join(td, "art"))
        back = FactorArtifact.load(path)
        assert back.W.shape == (M, K)        # padding sliced off on save
        np.testing.assert_array_equal(np.asarray(back.W), W_TRUE)
        resharded = FactorArtifact.load(path, mesh=MESH8)
        assert resharded.valid_rows == M
    # transposed() must not leak pad rows into the fold factor
    t = art.transposed()
    assert t.H.shape == (K, M)


@check("mesh_server_end_to_end_with_hot_swap")
def _():
    # fold-in codes depend only on H: halving H doubles every code, an
    # observable swap effect (2 w_i · H/2 = a_i exactly)
    art2 = FactorArtifact.from_factors(W_TRUE,
                                       (H_TRUE / 2.0).astype(np.float32),
                                       algo="bpp")
    with MeshServer(ART, mesh=MESH8, max_batch=16, chunk=32,
                    max_delay_s=1e-3) as srv:
        futs = [srv.submit(ROWS[i]) for i in range(10)]
        codes = np.stack([np.asarray(f.result(timeout=60)) for f in futs])
        np.testing.assert_allclose(codes, W_TRUE[:10], atol=5e-3, rtol=5e-3)
        scores, idx = srv.retrieve(ROWS[:6], k=3)
        assert (np.asarray(idx)[:, 0] == np.arange(6)).all(), \
            "each exact A row must retrieve its own W row first"
        stop = threading.Event()
        errs = []

        def client():
            while not stop.is_set():
                try:
                    srv.submit(ROWS[0]).result(timeout=60)
                except Exception as e:       # noqa: BLE001
                    errs.append(e)
                    return
        threads = [threading.Thread(target=client) for _ in range(3)]
        for t in threads:
            t.start()
        srv.swap(art2)                       # hot-reload under live traffic
        stop.set()
        for t in threads:
            t.join(timeout=60)
        assert not errs, errs
        code = np.asarray(srv.submit(ROWS[0]).result(timeout=60))
        np.testing.assert_allclose(code, 2.0 * W_TRUE[0], atol=1e-2,
                                   rtol=5e-3)


print(f"{len(FAILURES)} failures", flush=True)
sys.exit(1 if FAILURES else 0)
