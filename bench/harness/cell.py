"""One run of one cell: set-up, the measured window, the traced reduction,
and the comparison with the plain reference that decides ``correct``.

The system under test is ``NMFSolver``'s segment API, the compiled
fixed-iteration loop that ``fit`` runs: ``prepare_state`` lays A and the
factors out, and ``run_segment`` advances them ``segment_iters``
iterations in one call.  Set-up runs one segment (it compiles the loop or
loads it from the cache) and keeps its factors: the window goes on from
there with the same state, and the reference follows that first segment
from the same initial factors once the window has closed.
"""

from __future__ import annotations

import math
import os
import shutil
import sys
import time

import numpy as np

from . import data, device, hlo, spec, work, xplane


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def seed_key(seed: int):
    """A PRNG key from any whole number: the low 32 bits seed it and the
    rest are folded in."""
    import jax
    if seed < 0:
        raise ValueError("--seed must be a whole number >= 0")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


class CompileCounter:
    """Backend compilations, from the events JAX records."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class Job:
    """A, the initial factors, the solver and its state for one cell."""

    def __init__(self, cell: spec.Cell, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from jax.experimental import sparse as jsparse
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        from repro.backends import SparseOps
        from repro.core.engine import NMFSolver

        cfg, tr = cell.config, cell.traffic
        self.cell = cell
        self.m, self.n = cfg["rows"], cfg["cols"]
        self.k, self.algo = tr["k"], tr["algo"]
        self.seg = tr["segment_iters"]
        pr, pc = cfg["grid"]
        self.mesh = Mesh(np.asarray(devices[:pr * pc]).reshape(pr, pc),
                         ("pr", "pc"))
        key = seed_key(seed)
        k_a, k_f = jax.random.split(key)
        self.triplets = None
        if cfg["matrix"] == "video_like":
            # Each device generates its own block on a grid; one chip
            # keeps the default placement, as the serial loop's outputs do.
            sharding = None if pr * pc == 1 else NamedSharding(
                self.mesh, P("pr", "pc"))
            self.A = data.video_like_matrix(k_a, self.m, self.n,
                                            sharding=sharding,
                                            **cfg["generator"])
            A_in = self.A
        elif cfg["matrix"] == "erdos_renyi":
            self.triplets = data.erdos_renyi_triplets(
                k_a, self.m, self.n, cfg["density"])
            rows, cols, vals = self.triplets
            A_in = jsparse.BCOO(
                (jnp.asarray(vals),
                 jnp.asarray(np.stack([rows, cols], axis=1))),
                shape=(self.m, self.n))
            self.A = None
        else:
            raise ValueError(f"unknown matrix {cfg['matrix']!r}")
        self.nnz = None if self.triplets is None else len(self.triplets[2])
        self.W0, self.H0 = data.initial_factors(
            k_f, self.m, self.n, self.k, positive_w=self.algo == "mu")

        if tr["backend"] == "sparse":
            backend = SparseOps(spmm_impl=tr["spmm_impl"])
        else:
            backend = tr["backend"]
        kw = {}
        if tr["schedule"] == "faun":
            from repro.core.faun import FaunGrid
            kw["grid"] = FaunGrid(mesh=self.mesh)
        self.solver = NMFSolver(self.k, algo=self.algo,
                                schedule=tr["schedule"], backend=backend,
                                max_iters=self.seg, **kw)
        self.rs = self.solver.prepare_state(A_in, W0=self.W0, H0=self.H0)
        del A_in

    def segment(self):
        import jax
        import jax.profiler as jprof
        with jprof.TraceAnnotation("bench.run_segment"):
            self.solver.run_segment(self.rs, self.seg)
        with jprof.TraceAnnotation("bench.block"):
            jax.block_until_ready((self.rs.W, self.rs.Ht))

    def counters(self) -> dict:
        """The update rule's own running counts, where it keeps any (BPP:
        rows re-solved with a Tikhonov term, rows clamped)."""
        import jax
        rule_state, _ = self.solver._schedule.split_state(self.rs.state)
        if not isinstance(rule_state, dict):
            return {}
        return {name: int(v) for name, v in
                jax.device_get(rule_state).items() if np.ndim(v) == 0}

    def a_shapes(self):
        """(dtype, dims) of A's buffers as one device's program sees them."""
        import jax
        out = []
        for leaf in jax.tree.leaves(self.rs.Arep):
            shape = leaf.sharding.shard_shape(leaf.shape)
            out.append((_HLO_DTYPES.get(str(leaf.dtype), str(leaf.dtype)),
                        tuple(shape)))
        return out

    def compiled_text(self) -> str:
        """HLO text of the compiled loop the window runs."""
        from repro.core import engine
        run = engine._cached_run(
            self.solver._schedule,
            engine.StoppingCriterion(max_iters=self.seg), self.solver.donate)
        rs = self.rs
        return run.lower(rs.Arep, rs.W, rs.Ht, rs.normA_sq, rs.state,
                         self.seg).compile().as_text()

    def products_work(self, peaks: dict) -> dict:
        pr, pc = self.cell.config["grid"]
        m_l, n_l = -(-self.m // pr), -(-self.n // pc)
        if self.nnz is not None:
            return work.spmm_products(self.nnz, self.m, self.n, self.k,
                                      peaks)
        return work.dense_products(m_l, n_l, self.k, peaks)


_HLO_DTYPES = {"float32": "f32", "int32": "s32", "bfloat16": "bf16",
               "float16": "f16", "int8": "s8", "uint32": "u32",
               "bool": "pred"}


def model_gap(W, Ht, Wr, Htr) -> float:
    """‖W Htᵀ − Wr Htrᵀ‖_F / ‖Wr Htrᵀ‖_F in float64, from k x k products
    (no m x n product)."""
    W, Ht, Wr, Htr = (np.asarray(x, np.float64) for x in (W, Ht, Wr, Htr))

    def tr(a, b, c, d):
        return float(np.vdot(a.T @ b, c.T @ d))
    sq = tr(W, W, Ht, Ht) - 2 * tr(W, Wr, Ht, Htr) + tr(Wr, Wr, Htr, Htr)
    return math.sqrt(max(sq, 0.0) / tr(Wr, Wr, Htr, Htr))


def compare(rels, W, Ht, ref) -> dict:
    """The numbers that decide ``correct``, from the first segment against
    the reference: the largest relative gap of a per-iteration relative
    error, the gap of W (the factor the segment's last W-step made), and
    the gap of the models W·Hᵀ."""
    Wr, Htr, rels_r = ref
    rels = np.asarray(rels, np.float64)
    rels_r = np.asarray(rels_r, np.float64)
    if rels.shape != rels_r.shape or not np.isfinite(rels).all():
        err = math.inf
    else:
        err = float(np.max(np.abs(rels - rels_r) / rels_r))
    W, Wr = np.asarray(W, np.float64), np.asarray(Wr, np.float64)
    w_gap = float(np.linalg.norm(W - Wr) / np.linalg.norm(Wr))
    gap = model_gap(W, Ht, Wr, Htr)
    return {name: v if math.isfinite(v) else math.inf for name, v in
            (("err_gap", err), ("w_gap", w_gap), ("model_gap", gap))}


def reference_problem(job: Job, precision: str = "highest"):
    ref = spec.reference_module(job.cell.config["reference"])
    if job.triplets is not None:
        rows, cols, vals = job.triplets
        return ref, ref.SparseProblem(rows, cols, vals, (job.m, job.n),
                                      precision)
    return ref, ref.DenseProblem(job.A, job.mesh, precision)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, devices,
        *, trace_dir: str | None = None, on_chip: bool = True):
    """One run; returns the result line's fields, without ``metrics`` for
    a rehearsal off the chip (``on_chip=False``)."""
    import jax.profiler as jprof

    compiles = CompileCounter()
    t0 = time.perf_counter()
    log(f"{cell.name}: set-up (seed {seed})")
    job = Job(cell, seed, devices)
    job.segment()
    first_rels = np.concatenate(job.rs.rel_history)
    first_W, first_Ht = job.rs.W, job.rs.Ht
    setup_s = time.perf_counter() - t0
    log(f"{cell.name}: set-up {setup_s:.3f} s, {compiles.count} compiles")

    classes = None
    if trace:
        text = job.compiled_text()
        classes = hlo.classify(text, job.a_shapes())
        trace_dir = trace_dir or os.path.join(spec.BENCH, ".trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        with open(os.path.join(trace_dir, "program.hlo.txt"), "w") as f:
            f.write(text)
        jprof.start_trace(trace_dir)
    before = compiles.count
    counts0 = job.counters()
    iters = 0
    with jprof.TraceAnnotation(xplane.WINDOW_SPAN):
        w0 = time.perf_counter()
        while True:
            job.segment()
            iters += job.seg
            if time.perf_counter() - w0 >= seconds:
                break
        window_s = time.perf_counter() - w0
    if trace:
        jprof.stop_trace()
    window_compiles = compiles.count - before
    counts = {name: v - counts0[name] for name, v in job.counters().items()}
    window_rels = np.concatenate(job.rs.rel_history[1:])
    failed = int(np.sum(~np.isfinite(window_rels)))
    peak = device.peak_bytes(devices)
    log(f"{cell.name}: window {window_s:.3f} s, {iters} iterations, "
        f"{window_compiles} compiles, peak {peak} B")

    work_info = job.products_work(device.peaks(devices[0].device_kind)) \
        if on_chip else None
    # Free the program's state before the reference runs.
    first_W, first_Ht = np.asarray(first_W), np.asarray(first_Ht)
    W0, Ht0 = job.W0, job.H0.T
    job.rs = None
    job.solver = None
    ref, problem = reference_problem(job)
    t_ref = time.perf_counter()
    Wr, Htr, rels_r = ref.fit(problem, cell.traffic["algo"], W0, Ht0,
                              job.seg)
    ref_s = time.perf_counter() - t_ref
    numbers = compare(first_rels, first_W, first_Ht, (Wr, Htr, rels_r))
    limits = cell.limits
    correct = all(math.isfinite(v) and v <= limits[k]
                  for k, v in numbers.items()) and failed == 0
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    log(f"{cell.name}: reference {ref_s:.3f} s over {job.seg} iterations")

    out = {"correct": bool(correct), "attempted": iters, "failed": failed,
           "device": device.describe(devices)}
    out["device"]["memory_peak_bytes"] = peak
    metrics = {}
    breakdown = None
    if on_chip and not trace:
        values = {"iter_ms": (window_s * 1e3 / iters, "ms"),
                  "peak_hbm_gb": (None if peak is None else peak / 1e9,
                                  "GB"),
                  "setup_s": (setup_s, "s")}
        for m in cell.metrics(False):
            v, unit = values[m["name"]]
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": unit}
    if on_chip and trace:
        reduced = reduce_trace(trace_dir, classes, iters, work_info)
        reduced["counters"] = counts
        out["device"]["busy_s"] = reduced["busy_s"]
        out["device"]["window_s"] = reduced["window_s"]
        for m in cell.metrics(True):
            v = spec.metric_reader(m["name"])(reduced)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        breakdown = reduced["breakdown"]
        log(f"{cell.name}: products bound by {work_info['bound']} "
            f"({work_info['seconds'] * 1e3:.4f} ms per iteration); "
            f"{len(classes.a_ops)} ops read A: "
            f"{sorted(classes.a_ops.items())}; collectives: "
            f"{sorted(classes.collectives.items())}")
    if on_chip:
        out["metrics"] = metrics
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["window_compiles"] = window_compiles
    out["compared"] = checks
    return out


def reduce_trace(trace_dir: str, classes, iters: int, work_info) -> dict:
    """What the per-layer readers read: per-device reductions of the
    traced window, averaged over the devices, per iteration."""
    path = xplane.find_xplane(trace_dir)
    tr = xplane.load(path)
    window_s = (tr.window[1] - tr.window[0]) * 1e-9
    per_dev = [xplane.reduce_device(ops, tr.window, classes)
               for _, ops in sorted(tr.devices.items())]
    n = max(len(per_dev), 1)

    def mean(attr):
        return sum(getattr(d, attr) for d in per_dev) / n

    top: dict = {}
    for d in per_dev:
        for name, s in d.top_ops.items():
            if name in classes.containers:     # a loop: its ops are listed
                continue
            tag = ("reads A" if name in classes.a_ops else "collective"
                   if name in classes.collectives else "other")
            key = f"{name} ({tag})"
            top[key] = top.get(key, 0.0) + s / n
    gaps = []
    for d in per_dev:
        for s, e in d.gaps:
            gaps.append(((e - s) * 1e-9,
                         xplane.innermost_span(tr.spans, (s + e) / 2)))
    gaps.sort(reverse=True)
    return {
        "iters": iters, "window_s": window_s, "busy_s": mean("busy"),
        "a_ops_s": mean("a_ops"), "a_custom_calls_s": mean("a_custom_calls"),
        "collectives_s": mean("collectives"),
        "collective_count": len(classes.collectives),
        "collectives_exposed_s": mean("collectives_exposed"),
        "factor_side_s": mean("factor_side"),
        "products": work_info,
        "breakdown": {
            "device_ops": [[name, s] for name, s in
                           sorted(top.items(), key=lambda x: -x[1])[:10]],
            "idle_gaps": [[span, s] for s, span in gaps[:10]]},
    }
