#!/usr/bin/env python3
"""Smoke run of the NMF system on a TPU, through the entry points a user
calls: ``NMFSolver.fit`` for training and ``repro.serve`` for fold-in and
top-k.  Each phase checks its results and prints one JSON line; the last
line of standard output is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py              # one chip: dense, serving, sparse
    python chip_smoke.py --chips 4    # four chips: faun vs naive only
    python chip_smoke.py --size small # every phase at reduced size, on TPU
    python chip_smoke.py --size tiny  # rehearsal at toy sizes, CPU allowed

Phases (shapes are one chip's share of the paper's Table I data, k = 50):

* ``dense``: the 126,675 × 13,824 fp32 row block (7.0 GB) of the 8 × 1
  grid over the paper's dense video matrix (1,013,400 × 13,824).  MU,
  HALS and BPP on the serial schedule, dense and Pallas backends.  Checks:
  finite curves that do not rise, MU/HALS against a plain fp32 jax.numpy
  loop at highest precision from the same factors, every final error
  against a float64 host recomputation, and ``tpu_custom_call`` in the
  Pallas step.  BPP's last half-step is checked against the original Gram:
  its KKT violation, its error against a plain HALS solve of the same
  problem, and the rows it regularised or clamped (``core/bpp.py``).
* ``serving``: a ``FactorArtifact`` from the dense BPP factors; 1,024 new
  video rows folded in with ``FoldInProjector`` (checked by NNLS
  optimality and against a plain HALS reference), and the top 10 of all
  W rows for 256 of them with ``TopK`` against a plain fp32 scoring.
* ``sparse``: the 43,200 × 28,800 block of the 4 × 4 grid over the
  paper's sparse synthetic matrix (172,800 × 115,200, density 1e-3), on
  ``backend="sparse"`` (its default SpMM, the Pallas kernel on TPU) and
  on its row-sorted SpMM kernel (``spmm_impl="sorted"``; the layout's
  padded size is reported) against ``backend="dense"`` on the same
  matrix densified.
* ``faun`` (``--chips 4`` only): MU and HALS with ``schedule="faun"``
  (Algorithm 3) on the 2 × 2 grid against ``schedule="naive"``
  (Algorithm 2) from the same factors, on 253,344 × 13,824 (a quarter of
  the video rows less 6, 14.0 GB: W's rows split four ways), generated
  shard by shard on the devices.

Data are made from ``--seed`` on the device.  The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` here.
Without a TPU the script stops at once (unless ``--size tiny``).
"""

from __future__ import annotations

import argparse
import faulthandler
import json
import math
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# Sizes: rows and columns of each phase's matrix, and the rank.
SIZES = {
    "full": dict(dense=(126_675, 13_824), fold_rows=1024, queries=256,
                 sparse=(43_200, 28_800), density=1e-3,
                 faun=(253_344, 13_824), k=50),
    # Every phase at a size a chip call runs in seconds, with local row
    # contractions still longer than one row block (backends/base.py).
    "small": dict(dense=(20_000, 9_216), fold_rows=256, queries=64,
                  sparse=(10_800, 7_200), density=1e-3,
                  faun=(40_000, 9_216), k=50),
    # Toy shapes for a CPU rehearsal (kernels in interpret mode).
    "tiny": dict(dense=(600, 96), fold_rows=32, queries=8,
                 sparse=(240, 160), density=5e-2,
                 faun=(400, 96), k=8),
}
ITERS = {"mu": 8, "hals": 8, "bpp": 3}   # BPP: ~10-30 s per iteration
SPARSE_ITERS = 4
TOPK = 10

# Tolerances, all absolute on relative errors (rel = ‖A − WH‖/‖A‖).
RISE_TOL = 1e-4      # a curve may not rise this far above its running min
REF_REL_TOL = 1e-4   # BPP on two backends, per iteration
FACTOR_TOL = 1e-3    # ‖W H − W' H'‖ / ‖W' H'‖, or ‖X − X'‖ / ‖X'‖
HOST64_TOL = 2e-5    # final device error vs the float64 host recomputation
PAIR_REL_TOL = 1e-5  # two paths over the same data (pallas/dense, ...)
KKT_TOL = 1e-3       # fold-in NNLS optimality, relative to max |R|
# BPP's last half-step, relative to each row's max |R|.  Rows re-solved
# with a Tikhonov term ρ·G_jj (core/bpp.py ESCALATION, first ρ = 1e-3) sit
# off their exact KKT point by ρ·G_jj·x_j ≤ ρ·max|R| on nonnegative data;
# a clamped row is off by O(1).
BPP_KKT_TOL = 2e-3
SCORE_TOL = 1e-5     # top-k cosine scores

# Per-phase time limit (seconds); past it the stacks are dumped and the
# process exits, so a hang says where it is.
PHASE_LIMIT = {"dense": 560, "serving": 180, "sparse": 300, "faun": 200}

SPARSE_SOURCE = ("MPI-FAUN (arXiv:1609.09154) sec. 6.1.1 sparse synthetic, "
                 "172,800 x 115,200 at density 1e-3; block (0,0) of a 4x4 "
                 "grid")
DENSE_SOURCE = ("MPI-FAUN (arXiv:1609.09154) Table I video, "
                "1,013,400 x 13,824; row block 0 of an 8x1 grid")


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


class Phase:
    """Checks, numbers and the compile counters of one phase."""

    def __init__(self, name: str):
        self.name, self.out, self.failed = name, {}, []

    def check(self, key: str, ok: bool, value=None) -> None:
        self.out.setdefault("checks", {})[key] = bool(ok)
        if value is not None:
            self.out[key] = value
        if not ok:
            self.failed.append(key)
            log(f"{self.name}: check {key} FAILED ({value})")


class CompileCounters:
    """Compile seconds and persistent-cache hits/misses, from the events
    JAX records."""

    def __init__(self, monitoring):
        self.compile_s = 0.0
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def snapshot(self):
        return (self.compile_s, self.hits, self.misses)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--size", choices=tuple(SIZES), default="full")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.util import env
    except ImportError as e:
        log(f"cannot import the repro package from {ROOT}/src: {e}")
        return 2
    if args.size == "tiny" and args.chips > 1:
        env.configure(host_device_count=args.chips)   # before jax import
    cache_dir = env.compile_cache(ROOT)

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and args.size != "tiny":
        log(f"no TPU found (JAX platform: {platform}); this run needs one")
        return 1
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, found "
            f"{len(devices)}")
        return 1
    used = devices[:args.chips]
    log(f"{len(used)} x {devices[0].device_kind} ({platform}), size "
        f"{args.size}, compile cache {cache_dir}")

    counters = CompileCounters(jax.monitoring)
    sizes = SIZES[args.size]
    ctx = {"seed": args.seed, "tpu": platform == "tpu"}
    phases = (("faun",) if args.chips == 4
              else ("dense", "serving", "sparse"))
    ok = True
    for name in phases:
        fn = {"dense": phase_dense, "serving": phase_serving,
              "sparse": phase_sparse, "faun": phase_faun}[name]
        if name == "serving" and "artifact_result" not in ctx:
            log("serving skipped: it needs the dense phase's factors")
            ok = False
            continue
        ph = Phase(name)
        before = counters.snapshot()
        t0 = time.perf_counter()
        faulthandler.dump_traceback_later(PHASE_LIMIT[name], exit=True)
        try:
            fn(ph, sizes, ctx, used)
        except Exception as e:  # noqa: BLE001 - report, fail the phase
            traceback.print_exc()
            ph.check("raised", False, f"{type(e).__name__}: {e}")
        finally:
            faulthandler.cancel_dump_traceback_later()
        after = counters.snapshot()
        ph.out.update(
            ok=not ph.failed,
            seconds=round(time.perf_counter() - t0, 3),
            compile_s=round(after[0] - before[0], 3),
            cache_hits=after[1] - before[1],
            cache_misses=after[2] - before[2],
            peak_bytes=[peak_bytes(d) for d in used])
        print(f"phase {name}: {json.dumps(ph.out)}", flush=True)
        ok &= not ph.failed
    last = {"ok": bool(ok),
            "device": {"platform": platform,
                       "kind": devices[0].device_kind,
                       "count": len(used)}}
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


def peak_bytes(device):
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Plain references: fp32 jax.numpy at highest precision, written apart
# from the engine so that they share no code with what they check.
# ---------------------------------------------------------------------------

def _refs():
    import jax
    import jax.numpy as jnp
    import numpy as np

    hi = jax.lax.Precision.HIGHEST
    eps = math.sqrt(float(np.finfo(np.float32).tiny))

    def mm(a, b):
        return jnp.matmul(a, b, precision=hi)

    def at_b(a, b):                      # aᵀ b without transposing a
        return jnp.einsum("mn,mk->nk", a, b, precision=hi)

    def hals(X, G, R, normalize):
        def col(i, X):
            gii = G[i, i]
            if normalize:
                x = jnp.maximum(X[:, i] * gii + R[:, i] - mm(X, G[:, i]), 0)
                nrm = jnp.sqrt(jnp.sum(x * x))
                x = jnp.where(nrm > 0, x / jnp.maximum(nrm, eps), x)
            else:
                x = jnp.maximum(X[:, i] + (R[:, i] - mm(X, G[:, i]))
                                / jnp.maximum(gii, eps), 0)
            return X.at[:, i].set(x)
        return jax.lax.fori_loop(0, G.shape[0], col, X)

    def mu(X, G, R):
        return X * (R / (mm(X, G) + eps))

    def fit(algo, iters):
        @jax.jit
        def run(A, W, Ht):
            normA = jnp.sum(A * A)

            def body(carry, _):
                W, Ht = carry
                G, R = mm(Ht.T, Ht), mm(A, Ht)
                W = mu(W, G, R) if algo == "mu" else hals(W, G, R, True)
                G, R = mm(W.T, W), at_b(A, W)
                Ht = mu(Ht, G, R) if algo == "mu" else hals(Ht, G, R, False)
                sq = normA - 2 * jnp.sum(R * Ht) + jnp.sum(G * mm(Ht.T, Ht))
                return (W, Ht), jnp.sqrt(jnp.maximum(sq, 0) / normA)

            (W, Ht), rels = jax.lax.scan(body, (W, Ht), None, length=iters)
            return W, Ht.T, rels
        return run

    return fit, hals, mm, at_b


def rel_diff(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def model_diff(W, H, W2, H2) -> float:
    """‖W H − W2 H2‖_F / ‖W2 H2‖_F in float64 from k × k products (no
    m × n product): the models agree even where the sequential HALS sweep
    moves scale between W's and H's columns."""
    import numpy as np
    W, H, W2, H2 = (np.asarray(x, np.float64) for x in (W, H, W2, H2))
    tr = lambda a, b, c, d: float(np.vdot(a.T @ b, c @ d.T))
    sq = tr(W, W, H, H) - 2 * tr(W, W2, H, H2) + tr(W2, W2, H2, H2)
    return math.sqrt(max(sq, 0.0) / tr(W2, W2, H2, H2))


def curve(ph: Phase, key: str, rels) -> None:
    """Finite, and no rise past RISE_TOL above the running minimum."""
    import numpy as np
    r = np.asarray(rels, np.float64)
    ph.out[key] = r.tolist()
    finite = bool(np.isfinite(r).all())
    ph.check(f"{key}_finite", finite)
    if finite and r.size > 1:
        rise = float(np.max(r[1:] - np.minimum.accumulate(r)[:-1]))
        ph.check(f"{key}_rise", rise <= RISE_TOL, rise)


def host_rel64(A, factors, block_rows: int = 8192):
    """‖A − WH‖/‖A‖ in float64 on the host for each (W, H), reading the
    device matrix A once, shard by shard and row block by row block.
    Slices are taken of each device's own shard: a slice of a sharded A
    would first gather all of it onto every device."""
    import numpy as np
    Ws = [np.asarray(W, np.float64) for W, _ in factors]
    Hs = [np.asarray(H, np.float64) for _, H in factors]
    k = Ws[0].shape[1]
    norm = 0.0
    cross = np.zeros(len(factors))
    seen = set()
    for shard in A.addressable_shards:
        rows, cols = (sl.indices(d) for sl, d in zip(shard.index, A.shape))
        if (rows, cols) in seen:              # a replica of a block read
            continue
        seen.add((rows, cols))
        c0, c1, _ = cols
        for r0 in range(rows[0], rows[1], block_rows):
            r1 = min(r0 + block_rows, rows[1])
            Ab = np.asarray(shard.data[r0 - rows[0]:r1 - rows[0]],
                            np.float64)
            norm += float(np.vdot(Ab, Ab))
            Wb = np.concatenate([W[r0:r1] for W in Ws], axis=1)
            WtA = Wb.T @ Ab                          # (k·runs, block cols)
            for i, H in enumerate(Hs):
                cross[i] += float(np.vdot(WtA[i * k:(i + 1) * k],
                                          H[:, c0:c1]))
    out = []
    for i, (W, H) in enumerate(zip(Ws, Hs)):
        quad = float(np.vdot(W.T @ W, H @ H.T))
        out.append(math.sqrt(max(norm - 2 * cross[i] + quad, 0.0) / norm))
    return out


def kernel_check(ph: Phase, ctx, key, solver, m, n, nnz=None) -> None:
    """The lowered step calls a compiled kernel (on TPU; a CPU rehearsal
    runs kernels in interpret mode and only records the answer)."""
    text = solver.lower_step(m, n, nnz=nnz).as_text()
    found = "tpu_custom_call" in text
    if ctx["tpu"]:
        ph.check(key, found)
    else:
        ph.out[key] = found


def init_factors(key, m, n, k, algo):
    """The engine's initial factors, made here so references share them."""
    import jax
    import jax.numpy as jnp
    H0 = jax.random.uniform(key, (k, n), jnp.float32)
    if algo == "mu":
        W0 = jax.random.uniform(jax.random.fold_in(key, 1), (m, k),
                                jnp.float32, minval=0.1, maxval=1.0)
    else:
        W0 = jnp.zeros((m, k), jnp.float32)
    return W0, H0


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_dense(ph: Phase, sizes, ctx, devices) -> None:
    import jax
    from repro.core.engine import NMFSolver
    from repro.data.pipeline import video_like_matrix

    (m, n), k = sizes["dense"], sizes["k"]
    key = jax.random.PRNGKey(ctx["seed"])
    ph.out.update(source=DENSE_SOURCE, shape=[m, n], k=k,
                  A_bytes=m * n * 4, iters=ITERS)
    log(f"dense: compiling + generating A {m} x {n}")
    A = video_like_matrix(key, m, n)
    A.block_until_ready()
    fit_ref, hals_ref, mm, at_b = _refs()
    peaks = ph.out.setdefault("peak_bytes_after", {})
    peaks["A"] = peak_bytes(devices[0])

    runs, refs = {}, {}
    for backend in ("dense", "pallas"):
        for algo in ("mu", "hals", "bpp"):
            name = f"{backend}_{algo}"
            W0, H0 = init_factors(jax.random.fold_in(key, 7), m, n, k, algo)
            solver = NMFSolver(k, algo=algo, backend=backend,
                               max_iters=ITERS[algo])
            log(f"dense: compiling + running {name}")
            t0 = time.perf_counter()
            res = solver.fit(A, W0=W0, H0=H0)
            jax.block_until_ready(res.W)
            ph.out[f"s_{name}"] = round(time.perf_counter() - t0, 3)
            curve(ph, f"rel_{name}", res.rel_errors)
            runs[name] = res
            peaks[name] = peak_bytes(devices[0])
            if backend == "pallas" and algo == "mu":
                log("dense: lowering the pallas step")
                kernel_check(ph, ctx, "pallas_step_has_kernel", solver, m, n)
            if backend == "dense" and algo in ("mu", "hals"):
                log(f"dense: compiling + running the {algo} reference")
                Wr, Hr, rels = fit_ref(algo, ITERS[algo])(A, W0, H0.T)
                ref = f"{algo}_vs_reference"
                # The reference's own error curve comes from single long
                # XLA dots, which drift ~1e-4 in rel on TPU: reported, and
                # the final errors are compared in float64 below.
                ph.out[f"{ref}_rel_curve"] = float(
                    abs(res.rel_errors - rels).max())
                ph.out[f"{ref}_W"] = rel_diff(res.W, Wr)
                ph.out[f"{ref}_H"] = rel_diff(res.H, Hr)
                d = model_diff(res.W, res.H, Wr, Hr)
                ph.check(f"{ref}_WH", d <= FACTOR_TOL, d)
                refs[algo] = (Wr, Hr)
                peaks[f"reference_{algo}"] = peak_bytes(devices[0])
            if algo == "bpp":
                counts = {c: int(v) for c, v in
                          res.extras["rule_state"].items()}
                ph.out[f"bpp_rows_{backend}"] = counts
                ph.check(f"bpp_no_clamped_rows_{backend}",
                         counts["clamped_rows"] == 0, counts["clamped_rows"])
            if backend == "dense" and algo == "bpp":
                log("dense: checking BPP's last half-step")
                bpp_last_half_step(ph, A, res, hals_ref, mm, at_b)
            if backend == "pallas":
                d = runs[f"dense_{algo}"]
                diff = float(abs(res.rel_errors - d.rel_errors).max())
                ph.check(f"pallas_vs_dense_{algo}_rel", diff <= PAIR_REL_TOL
                         if algo != "bpp" else diff <= REF_REL_TOL, diff)

    log("dense: float64 host recomputation of every final error")
    pairs = [(r.W, r.H) for r in runs.values()] + list(refs.values())
    rel64 = host_rel64(A, pairs)
    for (name, r), v in zip(runs.items(), rel64):
        diff = abs(float(r.rel_errors[-1]) - v)
        ph.out[f"rel64_{name}"] = v
        ph.check(f"host64_{name}", diff <= HOST64_TOL, diff)
    for algo, v in zip(refs, rel64[len(runs):]):
        diff = abs(v - ph.out[f"rel64_dense_{algo}"])
        ph.check(f"{algo}_vs_reference_rel64", diff <= HOST64_TOL, diff)
    ctx["artifact_result"] = runs["dense_bpp"]
    ctx["dense_A_shape"] = (m, n)


def bpp_last_half_step(ph: Phase, A, res, hals_ref, mm, at_b) -> None:
    """BPP's final H-step against the problem it solved, min ‖A − W Hᵀ‖
    over H ≥ 0 for the final W: KKT with the original Gram WᵀW (no
    regularisation), and the relative error against 300 plain HALS sweeps
    on the same (G, R)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    W = jnp.asarray(res.W, jnp.float32)
    G, R = mm(W.T, W), at_b(A, W)
    Xr = jax.jit(lambda G, R: jax.lax.fori_loop(
        0, 300, lambda _, X: hals_ref(X, G, R, False),
        jnp.zeros_like(R)))(G, R)
    normA = float(jax.jit(lambda A: jnp.sum(A * A))(A))
    Gd, Rd = np.asarray(G, np.float64), np.asarray(R, np.float64)
    X = np.asarray(res.H, np.float64).T
    Y = X @ Gd - Rd
    scale = np.maximum(np.abs(Rd).max(axis=1, keepdims=True), 1e-30)
    viol = float((np.where(X > 0, np.abs(Y), np.maximum(-Y, 0))
                  / scale).max())
    ph.check("bpp_last_half_step_kkt", viol <= BPP_KKT_TOL, viol)

    def rel(X):
        sq = normA - 2 * np.vdot(X, Rd) + np.vdot(X @ Gd, X)
        return math.sqrt(max(sq, 0.0) / normA)

    r_bpp, r_hals = rel(X), rel(np.asarray(Xr, np.float64))
    ph.out["bpp_last_half_step_rel"] = r_bpp
    ph.out["hals300_last_half_step_rel"] = r_hals
    ph.check("bpp_last_half_step_vs_hals", r_bpp <= r_hals + PAIR_REL_TOL,
             r_bpp - r_hals)


def phase_serving(ph: Phase, sizes, ctx, devices) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.pipeline import video_like_matrix
    from repro.serve.artifact import FactorArtifact
    from repro.serve.foldin import FoldInProjector
    from repro.serve.topk import TopK

    m, n = ctx["dense_A_shape"]
    b, nq = sizes["fold_rows"], sizes["queries"]
    art = FactorArtifact.from_result(ctx.pop("artifact_result"))
    key = jax.random.PRNGKey(ctx["seed"])
    rows = video_like_matrix(key, b, n, row0=m)          # new frames
    _, hals_ref, mm, _ = _refs()

    log(f"serving: compiling + folding in {b} rows")
    proj = FoldInProjector(art, max_batch=b)
    X = np.asarray(proj.project(rows), np.float64)
    ph.out.update(fold_rows=b, fold_algo=proj.algo)

    H = jnp.asarray(art.H, jnp.float32)
    G = mm(H, H.T)
    R = mm(rows, H.T)
    Gd, Rd = np.asarray(G, np.float64), np.asarray(R, np.float64)
    grad = X @ Gd - Rd
    scale = float(np.abs(Rd).max())
    ph.check("fold_nonneg", float(X.min()) >= 0.0, float(X.min()))
    ph.check("fold_kkt_dual", float(grad.min()) >= -KKT_TOL * scale,
             float(grad.min()) / scale)
    comp = float(np.abs(X * grad).max()) / (scale * max(X.max(), 1e-30))
    ph.check("fold_kkt_complementarity", comp <= KKT_TOL, comp)

    log("serving: compiling + running the plain HALS fold-in reference")
    Xr = jax.jit(lambda G, R: jax.lax.fori_loop(
        0, 300, lambda _, X: hals_ref(X, G, R, False),
        jnp.zeros_like(R)))(G, R)
    Xr = np.asarray(Xr, np.float64)
    rows64 = np.asarray(rows, np.float64)
    Hd = np.asarray(H, np.float64)
    obj = lambda Z: float(np.sum((rows64 - Z @ Hd) ** 2))
    f_fold, f_ref = obj(X), obj(Xr)
    ph.check("fold_objective_vs_reference", f_fold <= f_ref * (1 + 1e-4),
             f_fold / f_ref)
    ph.out["fold_codes_vs_reference"] = rel_diff(X, Xr)
    ph.out["fold_rel_error"] = math.sqrt(f_fold / float(np.sum(rows64 ** 2)))

    log(f"serving: compiling + top-{TOPK} over {m} rows for {nq} queries")
    topk = TopK(art)
    Q = jnp.asarray(X[:nq], jnp.float32)
    scores, idx = topk.query(Q, k=TOPK)
    scores, idx = np.asarray(scores, np.float64), np.asarray(idx)
    W = jnp.asarray(art.W, jnp.float32)
    wn = jnp.sqrt(jnp.sum(mm(W, G) * W, axis=1))
    qn = jnp.sqrt(jnp.sum(mm(Q, G) * Q, axis=1))
    S = np.asarray(mm(mm(Q, G), W.T) / (qn[:, None] * wn[None, :]),
                   np.float64)
    best = -np.sort(-S, axis=1)[:, :TOPK]
    at_idx = np.take_along_axis(S, idx, axis=1)
    ph.out.update(topk_rows=m, topk_queries=nq, topk_k=TOPK)
    ph.check("topk_sorted", bool((np.diff(scores, axis=1) <= 0).all()))
    err = float(np.abs(scores - at_idx).max())
    ph.check("topk_scores_match_reference", err <= SCORE_TOL, err)
    short = float((best - at_idx).max())
    ph.check("topk_is_a_reference_topk", short <= SCORE_TOL, short)
    ref_idx = np.argsort(-S, axis=1, kind="stable")[:, :TOPK]
    ph.out["topk_index_mismatches"] = int((ref_idx != idx).sum())


def phase_sparse(ph: Phase, sizes, ctx, devices) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.backends import SparseOps
    from repro.core.engine import NMFSolver
    from repro.data.pipeline import erdos_renyi_bcoo

    (m, n), k, dens = sizes["sparse"], sizes["k"], sizes["density"]
    key = jax.random.fold_in(jax.random.PRNGKey(ctx["seed"]), 3)
    log(f"sparse: sampling {m} x {n} at density {dens}")
    A = erdos_renyi_bcoo(key, m, n, dens)
    nnz = int(A.nse)
    ph.out.update(source=SPARSE_SOURCE, shape=[m, n], density=dens,
                  nnz=nnz, k=k, iters=SPARSE_ITERS)
    W0, H0 = init_factors(jax.random.fold_in(key, 7), m, n, k, "bpp")

    sp = NMFSolver(k, algo="bpp", backend="sparse", max_iters=SPARSE_ITERS)
    log("sparse: lowering the sparse step")
    kernel_check(ph, ctx, "sparse_step_has_kernel", sp, m, n, nnz=nnz)
    log("sparse: compiling + running backend=sparse")
    t0 = time.perf_counter()
    rs = sp.fit(A, W0=W0, H0=H0)
    jax.block_until_ready(rs.W)
    ph.out["s_sparse"] = round(time.perf_counter() - t0, 3)
    curve(ph, "rel_sparse", rs.rel_errors)

    log("sparse: sorting A and compiling + running spmm_impl=sorted")
    ops = SparseOps(spmm_impl="sorted")
    blk = ops.prepare(A)
    ph.out.update(sorted_align=blk.align, sorted_unit_rows=blk.unit_rows,
                  sorted_slots=int(blk.vals.shape[-1]),
                  sorted_t_slots=int(blk.t_vals.shape[-1]))
    sp_sorted = NMFSolver(k, algo="bpp", backend=ops, max_iters=SPARSE_ITERS)
    kernel_check(ph, ctx, "sorted_step_has_kernel", sp_sorted, m, n, nnz=nnz)
    t0 = time.perf_counter()
    rso = sp_sorted.fit(blk, W0=W0, H0=H0)
    jax.block_until_ready(rso.W)
    ph.out["s_sorted"] = round(time.perf_counter() - t0, 3)
    del blk
    curve(ph, "rel_sorted", rso.rel_errors)
    diff = float(jnp.abs(rso.rel_errors - rs.rel_errors).max())
    ph.check("sorted_vs_sparse_rel", diff <= PAIR_REL_TOL, diff)
    for f in ("W", "H"):
        d = rel_diff(getattr(rso, f), getattr(rs, f))
        ph.check(f"sorted_vs_sparse_{f}", d <= FACTOR_TOL, d)

    log("sparse: compiling + running backend=dense on A densified")
    Ad = A.todense()
    t0 = time.perf_counter()
    rd = NMFSolver(k, algo="bpp", max_iters=SPARSE_ITERS).fit(Ad, W0=W0,
                                                              H0=H0)
    jax.block_until_ready(rd.W)
    ph.out["s_dense"] = round(time.perf_counter() - t0, 3)
    del Ad
    curve(ph, "rel_dense", rd.rel_errors)
    diff = float(jnp.abs(rs.rel_errors - rd.rel_errors).max())
    ph.check("sparse_vs_dense_rel", diff <= PAIR_REL_TOL, diff)
    for f in ("W", "H"):
        d = rel_diff(getattr(rs, f), getattr(rd, f))
        ph.check(f"sparse_vs_dense_{f}", d <= FACTOR_TOL, d)

    log("sparse: float64 host recomputation of the final errors")
    idx = np.asarray(A.indices)
    v = np.asarray(A.data, np.float64)
    norm = float(v @ v)
    for name, r in (("sparse", rs), ("dense", rd)):
        W = np.asarray(r.W, np.float64)
        H = np.asarray(r.H, np.float64)
        cross = float(v @ np.einsum("ij,ij->i", W[idx[:, 0]],
                                    H.T[idx[:, 1]]))
        quad = float(np.vdot(W.T @ W, H @ H.T))
        rel64 = math.sqrt(max(norm - 2 * cross + quad, 0.0) / norm)
        diff = abs(float(r.rel_errors[-1]) - rel64)
        ph.out[f"rel64_{name}"] = rel64
        ph.check(f"host64_{name}", diff <= HOST64_TOL, diff)


def phase_faun(ph: Phase, sizes, ctx, devices) -> None:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import faun
    from repro.core.engine import NMFSolver
    from repro.data.pipeline import video_like_matrix
    from repro.util.compat import make_mesh

    (m, n), k = sizes["faun"], sizes["k"]
    grid = faun.make_faun_mesh(2, 2, devices=devices)
    mesh = make_mesh((4,), ("p",), devices=devices)
    key = jax.random.PRNGKey(ctx["seed"])
    ph.out.update(source=DENSE_SOURCE.replace(
        "row block 0 of an 8x1 grid",
        "row blocks 0-1 of an 8x1 grid, less 6 rows"),
        shape=[m, n], k=k, A_bytes=m * n * 4, grid=[2, 2], iters=ITERS)
    # Each schedule gets A generated in its own layout (the same values:
    # a row is a function of the seed and its index).  Resharding the 2 × 2
    # blocks into naive's copies instead would hold three layouts at once.
    layouts = {"faun": (dict(grid=grid), grid.sharding(grid.spec_A())),
               "naive": (dict(mesh=mesh), NamedSharding(mesh, P("p", None)))}
    init = {algo: init_factors(jax.random.fold_in(key, 7), m, n, k, algo)
            for algo in ("mu", "hals")}
    runs, A = {}, None
    for sched, (kw, sharding) in layouts.items():
        if A is not None:
            A.delete()
        log(f"faun: compiling + generating A {m} x {n} for {sched}, "
            f"shard by shard")
        A = video_like_matrix(key, m, n, sharding=sharding)
        A.block_until_ready()
        if sched == "faun":
            check_quarters(ph, A, m * n * 4, devices)
        for algo, (W0, H0) in init.items():
            solver = NMFSolver(k, algo=algo, schedule=sched,
                               max_iters=ITERS[algo], **kw)
            log(f"faun: compiling + running {sched} {algo}")
            t0 = time.perf_counter()
            res = solver.fit(A, W0=W0, H0=H0)
            jax.block_until_ready(res.W)
            ph.out[f"s_{sched}_{algo}"] = round(time.perf_counter() - t0, 3)
            curve(ph, f"rel_{sched}_{algo}", res.rel_errors)
            runs[f"{sched}_{algo}"] = res

    for algo in init:
        f, nv = runs[f"faun_{algo}"], runs[f"naive_{algo}"]
        # The two schedules' meshes order the devices differently: compare
        # on the host.
        diff = float(np.abs(np.asarray(f.rel_errors)
                            - np.asarray(nv.rel_errors)).max())
        ph.check(f"faun_vs_naive_{algo}_rel", diff <= PAIR_REL_TOL, diff)
        for fac in ("W", "H"):
            d = rel_diff(getattr(f, fac), getattr(nv, fac))
            ph.check(f"faun_vs_naive_{algo}_{fac}", d <= FACTOR_TOL, d)

    log("faun: float64 host recomputation of every final error")
    rel64 = host_rel64(A, [(r.W, r.H) for r in runs.values()])
    for (name, r), v in zip(runs.items(), rel64):
        diff = abs(float(r.rel_errors[-1]) - v)
        ph.out[f"rel64_{name}"] = v
        ph.check(f"host64_{name}", diff <= HOST64_TOL, diff)


def check_quarters(ph: Phase, A, a_bytes: int, devices) -> None:
    """A is split over every device, a quarter on each (not all on the
    first), by the devices' own memory accounting."""
    in_use = [d.memory_stats()["bytes_in_use"]
              if d.memory_stats() else None for d in devices]
    ph.out["bytes_in_use_after_A"] = in_use
    shard_devs = {s.device for s in A.addressable_shards}
    ph.check("A_on_every_device", shard_devs == set(devices),
             len(shard_devs))
    if None not in in_use:
        spread = [b / (a_bytes / len(devices)) for b in in_use]
        ph.check("A_quarter_per_device",
                 all(0.95 <= s <= 1.1 for s in spread), spread)


if __name__ == "__main__":
    sys.exit(main())
