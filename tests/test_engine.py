"""core.engine.NMFSolver: serial parity with the legacy drivers, sparse
backends, stopping criteria, BlockCOO storage, and cost-model threading.

Single-device smoke tier here; the multi-device engine checks run in a
subprocess (engine_distributed_checks.py) and are marked ``slow``, so
``pytest -m "not slow"`` finishes in minutes.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import sparse as jsparse

from repro.core import aunmf, blocksparse, costmodel
from repro.core.engine import NMFSolver, StoppingCriterion
from repro.data.pipeline import erdos_renyi_bcoo, erdos_renyi_matrix, \
    lowrank_matrix

KEY = jax.random.PRNGKey(0)
A = lowrank_matrix(KEY, 120, 90, 8, noise=0.01)

HERE = os.path.dirname(__file__)


# ----------------------------------------------------------------- parity

@pytest.mark.parametrize("algo", ["mu", "hals", "bpp"])
def test_serial_engine_bitmatches_reference_loop(algo):
    """NMFSolver(schedule="serial") must reproduce a hand-rolled python loop
    over aunmf_step bit-for-bit (the old aunmf.fit behaviour).

    The loop carries H transposed, (n, k), as the engine does: XLA picks
    the dot's summation order from its operands' layouts, so a loop that
    carries (k, n) differs from the engine in the last bit."""
    from repro.core import algorithms
    from repro.core.error import sq_frobenius

    k, iters = 8, 10
    H0 = aunmf.init_h(KEY, A.shape[1], k)
    W0 = aunmf.init_w(jax.random.fold_in(KEY, 1), A.shape[0], k, algo)

    update_w, update_h = algorithms.get_update_fns(algo)
    normA_sq = sq_frobenius(A)

    @jax.jit
    def step(A, W, Ht):
        W, H, _ = aunmf.aunmf_step(A, W, Ht.T, update_w, update_h, normA_sq)
        return W, H.T

    W, Ht = W0, jnp.asarray(H0).T
    for _ in range(iters):
        W, Ht = step(A, W, Ht)

    res = NMFSolver(k, algo=algo, max_iters=iters).fit(A, key=KEY)
    np.testing.assert_array_equal(np.asarray(res.W), np.asarray(W))
    np.testing.assert_array_equal(np.asarray(res.H), np.asarray(Ht.T))


def test_legacy_fit_is_engine_wrapper():
    res = aunmf.fit(A, 6, algo="bpp", iters=8, key=KEY)
    eng = NMFSolver(6, algo="bpp", max_iters=8).fit(A, key=KEY)
    np.testing.assert_array_equal(np.asarray(res.W), np.asarray(eng.W))
    assert eng.extras["schedule"] == "serial"
    assert eng.extras["backend"] == "dense"


def test_serial_pallas_backend_matches_dense():
    dense = NMFSolver(6, algo="mu", max_iters=8).fit(A, key=KEY)
    pallas = NMFSolver(6, algo="mu", backend="pallas", max_iters=8) \
        .fit(A, key=KEY)
    np.testing.assert_allclose(np.asarray(dense.W), np.asarray(pallas.W),
                               atol=2e-4)


def test_serial_sparse_backend_matches_dense():
    Ad = erdos_renyi_matrix(KEY, 96, 72, 0.25)
    As = jsparse.BCOO.fromdense(Ad)
    dense = NMFSolver(6, algo="mu", max_iters=8).fit(Ad, key=KEY)
    sp = NMFSolver(6, algo="mu", backend="sparse", max_iters=8) \
        .fit(As, key=KEY)
    np.testing.assert_allclose(np.asarray(dense.W), np.asarray(sp.W),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(dense.rel_errors),
                               np.asarray(sp.rel_errors), atol=1e-5)


def test_sparse_backend_densifies_dense_input():
    """backend="sparse" accepts a dense array and converts internally."""
    Ad = erdos_renyi_matrix(KEY, 64, 48, 0.2)
    r1 = NMFSolver(4, algo="mu", backend="sparse", max_iters=5).fit(Ad,
                                                                    key=KEY)
    r2 = NMFSolver(4, algo="mu", max_iters=5).fit(Ad, key=KEY)
    np.testing.assert_allclose(np.asarray(r1.W), np.asarray(r2.W), atol=2e-4)


# ------------------------------------------------------------ stopping

def test_tolerance_stops_before_max_iters():
    A0 = lowrank_matrix(jax.random.fold_in(KEY, 5), 80, 60, 4, noise=0.0)
    res = NMFSolver(8, algo="bpp", max_iters=300, tol=1e-4).fit(A0, key=KEY)
    assert res.extras["stopped_early"]
    assert res.iters < 300
    assert res.rel_errors.shape == (res.iters,)
    assert float(res.rel_errors[-1]) <= 1e-4


def test_stall_detection_stops():
    A0 = lowrank_matrix(jax.random.fold_in(KEY, 5), 80, 60, 4, noise=0.0)
    res = NMFSolver(8, algo="bpp", max_iters=300, stall_iters=5,
                    stall_tol=1e-7).fit(A0, key=KEY)
    assert res.extras["stopped_early"]
    assert res.iters < 300


def test_fixed_iteration_run_matches_adaptive_prefix():
    """With an unreachable tol the adaptive loop runs all max_iters and must
    agree with the scan-based fixed loop."""
    fixed = NMFSolver(6, algo="mu", max_iters=10).fit(A, key=KEY)
    adaptive = NMFSolver(6, algo="mu", max_iters=10, tol=1e-12).fit(A,
                                                                    key=KEY)
    assert adaptive.iters == 10
    np.testing.assert_allclose(np.asarray(fixed.rel_errors),
                               np.asarray(adaptive.rel_errors), atol=1e-6)


def test_stopping_criterion_flags():
    assert not StoppingCriterion().adaptive
    assert StoppingCriterion(tol=1e-3).adaptive
    assert StoppingCriterion(stall_iters=2).adaptive


# ------------------------------------------------------------ blocksparse

def test_blockcoo_roundtrip():
    Ad = erdos_renyi_matrix(KEY, 48, 36, 0.3)
    blk = blocksparse.blockify(Ad, 2, 2)
    assert blk.grid == (2, 2)
    np.testing.assert_allclose(blk.todense(), np.asarray(Ad), atol=0)


def test_blockcoo_local_spmm():
    Ad = erdos_renyi_matrix(KEY, 40, 30, 0.3)
    blk = blocksparse.blockify(Ad, 1, 1)
    B = jax.random.normal(jax.random.fold_in(KEY, 1), (30, 5))
    C = jax.random.normal(jax.random.fold_in(KEY, 2), (40, 5))
    np.testing.assert_allclose(
        np.asarray(blocksparse.local_spmm(blk, B)), np.asarray(Ad @ B),
        atol=1e-4)
    np.testing.assert_allclose(
        np.asarray(blocksparse.local_spmm_t(blk, C)), np.asarray(Ad.T @ C),
        atol=1e-4)


def test_blockcoo_rejects_bad_grid():
    Ad = erdos_renyi_matrix(KEY, 40, 30, 0.3)
    with pytest.raises(ValueError):
        blocksparse.blockify(Ad, 3, 2)       # 40 % 3 != 0


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16])
def test_erdos_renyi_bcoo_matches_dense_variant(dt):
    """Shared-sampler round trip: the same key must yield the same matrix
    in dense, BCOO, and BlockCOO form, bit for bit."""
    Ad = erdos_renyi_matrix(KEY, 64, 48, 0.1, dtype=dt)
    As = erdos_renyi_bcoo(KEY, 64, 48, 0.1, dtype=dt)
    np.testing.assert_array_equal(np.asarray(As.todense(), np.float32),
                                  np.asarray(Ad, np.float32))
    ref = jsparse.BCOO.fromdense(Ad)
    np.testing.assert_array_equal(np.asarray(As.indices),
                                  np.asarray(ref.indices))
    np.testing.assert_array_equal(np.asarray(As.data, np.float32),
                                  np.asarray(ref.data, np.float32))
    blk = blocksparse.blockify(As, 2, 2)
    np.testing.assert_array_equal(blk.todense().astype(np.float32),
                                  np.asarray(Ad, np.float32))


# ------------------------------------------------------------- cost model

def test_schedule_cost_threads_nnz():
    m, n, k, nnz = 100_000, 80_000, 32, 10_000_000
    dense = costmodel.schedule_cost("faun", m, n, k, pr=8, pc=8)
    sp = costmodel.schedule_cost("faun", m, n, k, pr=8, pc=8, dense=False,
                                 nnz=nnz)
    assert sp.flops < dense.flops
    assert sp.memory_words < dense.memory_words
    assert sp.words == dense.words      # panels are dense either way
    serial = costmodel.schedule_cost("serial", m, n, k)
    assert serial.words == 0 and serial.messages == 0
    naive = costmodel.schedule_cost("naive", m, n, k, pr=64)
    assert naive.words > dense.words    # full-factor gathers


def test_solver_predict_cost():
    s = NMFSolver(16, algo="mu")
    c = s.predict_cost(10_000, 8_000)
    assert c.flops > 0 and c.words == 0


# ------------------------------------------- schedule × backend matrix

SCHEDULE_KWARGS = {
    "serial": {},
    "faun": {},          # 1×1 grid on the single smoke-tier device
    "naive": {},
    "gspmd": {},
}


@pytest.mark.parametrize("schedule", sorted(SCHEDULE_KWARGS))
@pytest.mark.parametrize("backend", ["dense", "pallas", "sparse"])
def test_schedule_backend_matrix_matches_serial_dense(schedule, backend):
    """Every (schedule, backend) cell must run through NMFSolver.fit and
    agree with the serial dense oracle on the same input (single device;
    the multi-device grid parity runs in engine_distributed_checks.py)."""
    Ad = erdos_renyi_matrix(KEY, 48, 36, 0.3)
    ref = NMFSolver(5, algo="mu", max_iters=6).fit(Ad, key=KEY)
    res = NMFSolver(5, algo="mu", schedule=schedule, backend=backend,
                    max_iters=6, **SCHEDULE_KWARGS[schedule]).fit(Ad, key=KEY)
    assert res.extras["schedule"] == schedule
    assert res.extras["backend"] == backend
    np.testing.assert_allclose(np.asarray(res.W), np.asarray(ref.W),
                               atol=2e-4)
    np.testing.assert_allclose(np.asarray(res.rel_errors),
                               np.asarray(ref.rel_errors), atol=1e-5)


def test_numpy_input_fit():
    """Legacy wrappers and both dense/sparse backends accept host numpy
    arrays (infer_backend classifies ndarray as dense)."""
    An = np.asarray(erdos_renyi_matrix(KEY, 32, 24, 0.3))
    ref = NMFSolver(4, algo="mu", max_iters=4).fit(jnp.asarray(An), key=KEY)
    res = aunmf.fit(An, 4, algo="mu", iters=4, key=KEY)
    assert res.extras["backend"] == "dense"
    np.testing.assert_array_equal(np.asarray(res.W), np.asarray(ref.W))
    sp = NMFSolver(4, algo="mu", backend="sparse", max_iters=4).fit(An,
                                                                    key=KEY)
    np.testing.assert_allclose(np.asarray(sp.W), np.asarray(ref.W),
                               atol=2e-4)


@pytest.mark.parametrize("backend", ["dense", "pallas", "sparse"])
def test_low_precision_input_fit(backend):
    """bf16 data matrices fit on every backend: local products accumulate
    fp32, the loop restores the bf16 factor carry."""
    Ab = lowrank_matrix(KEY, 64, 48, 4, noise=0.01).astype(jnp.bfloat16)
    res = NMFSolver(4, algo="mu", backend=backend, max_iters=4).fit(Ab,
                                                                    key=KEY)
    assert res.W.dtype == jnp.bfloat16
    assert np.isfinite(np.asarray(res.rel_errors, np.float32)).all()


# ------------------------------------------------------ row contractions

@pytest.mark.parametrize("m", [16, 50, 64])
def test_contract_rows_blocks_match_one_dot(m):
    """Row blocks (the last one shifted back to end at row m, its repeated
    rows zeroed) sum to the one-dot Aᵀ·B."""
    from repro.backends.base import contract_rows
    Am = jax.random.uniform(KEY, (m, 12))
    B = jax.random.uniform(jax.random.fold_in(KEY, 1), (m, 5))
    np.testing.assert_allclose(np.asarray(contract_rows(Am, B, 16)),
                               np.asarray(contract_rows(Am, B, None)),
                               rtol=1e-6)


@pytest.mark.parametrize("backend", ["dense", "sparse"])
def test_global_view_ops_contract_rows_in_one_dot(backend):
    """gspmd's variant of a backend contracts rows in one dot (a dynamic
    slice along a sharded dim would be gathered), and is its own fixed
    point with a distinct compile-cache key."""
    from repro.backends import get_backend
    from repro.backends.base import ROW_BLOCK
    ops = get_backend(backend)
    g = ops.global_view_ops()
    assert ops.row_block == ROW_BLOCK and g.row_block is None
    assert g.global_view_ops() is g
    assert g.cache_key() != ops.cache_key()


# ------------------------------------------------------ LocalOps registry

def test_backend_registry_accepts_instance_and_class():
    from repro.backends import DenseOps

    res_name = NMFSolver(4, algo="mu", backend="dense", max_iters=4) \
        .fit(A, key=KEY)
    for spec in (DenseOps(), DenseOps):      # instance and class
        res = NMFSolver(4, algo="mu", backend=spec, max_iters=4) \
            .fit(A, key=KEY)
        assert res.extras["backend"] == "dense"
        np.testing.assert_array_equal(np.asarray(res.W),
                                      np.asarray(res_name.W))


def test_custom_backend_registration():
    from repro import backends

    calls = []

    class TracingOps(backends.DenseOps):
        name = "tracing"

        def mm(self, A_, B):
            calls.append("mm")
            return super().mm(A_, B)

    backends.register_backend("tracing", TracingOps, overwrite=True)
    try:
        assert "tracing" in backends.available_backends()
        res = NMFSolver(4, algo="mu", backend="tracing", max_iters=3) \
            .fit(A, key=KEY)
        assert res.extras["backend"] == "tracing"
        assert calls  # the schedule consumed the custom LocalOps
        ref = NMFSolver(4, algo="mu", max_iters=3).fit(A, key=KEY)
        np.testing.assert_array_equal(np.asarray(res.W), np.asarray(ref.W))
    finally:
        from repro.backends import base
        base._REGISTRY.pop("tracing", None)


def test_register_backend_rejects_duplicates():
    from repro import backends
    with pytest.raises(ValueError):
        backends.register_backend("dense", backends.DenseOps)


# ----------------------------------------------------------- validation

def test_bad_schedule_and_backend_rejected():
    with pytest.raises(ValueError):
        NMFSolver(4, schedule="mpi")
    with pytest.raises(ValueError):
        NMFSolver(4, backend="cusparse")
    with pytest.raises(ValueError):           # sparse SpMM is fp32-only
        NMFSolver(4, backend="sparse", panel_dtype=jnp.bfloat16)
    with pytest.raises(ValueError):           # dense backends need dense A
        As = jsparse.BCOO.fromdense(erdos_renyi_matrix(KEY, 16, 12, 0.3))
        NMFSolver(4, algo="mu", max_iters=2).fit(As, key=KEY)


def test_serial_lower_step_smoke():
    low = NMFSolver(4, algo="mu").lower_step(32, 24)
    assert "dot" in low.as_text()


def test_serial_sparse_lower_step():
    """The 1×1-grid BlockCOO representation makes serial sparse AOT-lowerable
    (the BCOO path could not carry abstract shapes)."""
    low = NMFSolver(4, algo="mu", backend="sparse").lower_step(32, 24, nnz=40)
    assert "scatter" in low.as_text()


# ------------------------------------------------- panel compression

def test_panel_compression_validation():
    from repro.core import faun
    grid = faun.make_faun_mesh(1, 1)
    with pytest.raises(ValueError, match="unknown panel_compression"):
        NMFSolver(4, schedule="faun", grid=grid, panel_compression="fp4")
    with pytest.raises(ValueError, match="serial"):
        NMFSolver(4, schedule="serial", panel_compression="int8")
    with pytest.raises(ValueError, match="do not compose"):
        NMFSolver(4, schedule="faun", grid=grid, panel_compression="int8",
                  panel_dtype=jnp.bfloat16)


def test_panel_compression_none_is_bit_identical():
    """The default (None) must not change the exact path at all — the
    compression indirection compiles away."""
    from repro.core import faun
    grid = faun.make_faun_mesh(1, 1)
    ref = NMFSolver(6, algo="mu", schedule="faun", grid=grid,
                    max_iters=8).fit(A, key=KEY)
    off = NMFSolver(6, algo="mu", schedule="faun", grid=grid, max_iters=8,
                    panel_compression=None).fit(A, key=KEY)
    np.testing.assert_array_equal(np.asarray(ref.W), np.asarray(off.W))
    assert "panel_residuals" not in off.extras


def test_panel_compression_single_device_faun():
    """A 1×1 grid exercises the quantisation numerics without real
    collectives: the compressed run converges next to the exact one and
    surfaces nonzero error-feedback residuals."""
    from repro.core import faun
    grid = faun.make_faun_mesh(1, 1)
    ex = NMFSolver(6, algo="mu", schedule="faun", grid=grid,
                   max_iters=20).fit(A, key=KEY)
    co = NMFSolver(6, algo="mu", schedule="faun", grid=grid, max_iters=20,
                   panel_compression="int8").fit(A, key=KEY)
    assert abs(float(co.rel_errors[-1]) - float(ex.rel_errors[-1])) < 5e-3
    res = co.extras["panel_residuals"]
    assert sorted(res) == ["gather_h", "gather_w", "gram_h", "gram_w",
                           "rs_h", "rs_w"]
    assert any(np.abs(np.asarray(v, np.float32)).max() > 0
               for v in res.values())


def test_predict_cost_reflects_compression():
    """Compressed panel words ≈ exact/4 + scale sidecars; Grams unchanged
    (int32 payload) + their pmax.  Verified against the closed forms."""
    from repro.core import faun
    from repro.distributed.compression import compressed_words
    m, n, k, pr, pc = 4096, 2048, 32, 4, 2
    p = pr * pc
    grid = faun.make_faun_mesh(1, 1)
    ex = costmodel.schedule_cost("faun", m, n, k, pr=pr, pc=pc, algo="mu")
    co = costmodel.schedule_cost("faun", m, n, k, pr=pr, pc=pc, algo="mu",
                                 compression="int8")
    panel_h, panel_w = (pr - 1) * n * k / p, (pc - 1) * m * k / p
    expect = (2 * 2 * k * k * (p - 1) / p + 2 * 2 * k * (p - 1) / p
              + compressed_words(panel_h, rows=(pr - 1) * n / p)
              + compressed_words(panel_w, rows=(pc - 1) * m / p)
              + compressed_words(panel_w, rows=(pc - 1) * m / p, scatter=True)
              + compressed_words(panel_h, rows=(pr - 1) * n / p, scatter=True))
    assert co.words == expect
    assert co.words < ex.words            # compression must actually win
    assert co.messages == 2 * ex.messages
    assert co.flops == ex.flops
    # naive: two full-factor gathers quarter + one scale word per row
    nex = costmodel.schedule_cost("naive", m, n, k, pr=p, algo="mu")
    nco = costmodel.schedule_cost("naive", m, n, k, pr=p, algo="mu",
                                  compression="int8")
    assert nco.words == nex.words / 4 + (m + n) * (p - 1) / p
    # the solver-level knob threads through predict_cost (pretend the 1×1
    # smoke-tier grid is 4×2 — predict_cost only reads its shape)
    s = NMFSolver(k, algo="mu", schedule="faun", grid=grid,
                  panel_compression="int8")
    s._schedule.grid_shape = lambda: (pr, pc)
    assert s.predict_cost(m, n).words == co.words


def test_compressed_words_helper():
    from repro.distributed.compression import compressed_words
    assert compressed_words(400.0, rows=10.0) == 110.0
    assert compressed_words(400.0, rows=10.0, scatter=True) == 120.0


def test_get_compressor_rejects_unknown():
    from repro.distributed.compression import get_compressor
    with pytest.raises(ValueError, match="unknown panel_compression"):
        get_compressor("int4")


# ------------------------------------------------- multi-device (slow tier)

@pytest.mark.slow
@pytest.mark.timeout(1200)
def test_engine_distributed_checks():
    """Runs engine_distributed_checks.py in one subprocess with 8 fake host
    devices (same harness as test_distributed.py)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "engine_distributed_checks.py")],
        capture_output=True, text=True, env=env, timeout=1150)
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "engine distributed checks failed"
    assert "0 failures" in proc.stdout
