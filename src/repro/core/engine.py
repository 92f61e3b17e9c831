"""Unified AU-NMF solver engine: one driver lifecycle, pluggable schedules
over a pluggable local-compute layer.

Before this module the four drivers (core/aunmf.py, core/faun.py,
core/naive.py, core/gspmd.py) each reimplemented factor init, device
placement, the ``lax.scan`` loop, error tracking, and result packing.
``NMFSolver`` owns that lifecycle once and composes three plug points:

* **schedule** — who computes which block of the four matrix products and
  which collectives move the k-width panels:

    - ``serial``  single-device oracle (paper Algorithm 1)
    - ``faun``    MPI-FAUN on a pr×pc grid (Algorithm 3, shard_map)
    - ``naive``   Naive-Parallel-AUNMF baseline (Algorithm 2, 1-D mesh)
    - ``gspmd``   global-view program, XLA's partitioner picks collectives

* **backend** — a ``repro.backends.LocalOps`` implementation of the purely
  local products (A·Hᵀ, AᵀW, XᵀX) and of A's storage representation:

    - ``dense``   plain XLA GEMMs (repro.backends.DenseOps)
    - ``pallas``  the repro.kernels TPU kernels (PallasOps)
    - ``sparse``  block-local COO SpMM (SparseOps over core/blocksparse.py;
                  on TPU it lowers to kernels/spmm.py); A's nonzeros never
                  cross the wire, per the paper's invariant

  ``backend=`` also accepts a LocalOps instance or subclass, or any name
  registered via ``repro.backends.register_backend`` — schedules consume
  only the LocalOps surface, so a custom backend works on every schedule.

* **algo** — a ``repro.core.rules.UpdateRule``: the local update
  computation both half-updates run, plus its serving fold-in, cost hooks,
  and optional carried state.  Built-ins: ``mu``, ``hals``,
  ``bpp``/``abpp``/``anls``, and the Gillis–Glineur accelerated
  ``amu``/``ahals``; ``algo=`` also accepts an UpdateRule instance or any
  name registered via ``repro.core.rules.register_algorithm`` — schedules
  consume only the UpdateRule surface, so a custom rule works on every
  schedule × backend cell (and in ``repro.serve`` fold-in) for free.

Support matrix (✓ everywhere):

    schedule \\ backend   dense   pallas   sparse
    serial                 ✓       ✓        ✓  (1×1-grid BlockCOO)
    faun                   ✓       ✓        ✓  (pr×pc BlockCOO)
    naive                  ✓       ✓        ✓  (row- + col-blocked copies)
    gspmd                  ✓       ✓*       ✓  (nnz-sharded triplets)

  (* gspmd/pallas is single-device only — multi-device grids raise: XLA's
  auto-partitioner cannot partition a pallas_call and would replicate A,
  which is itself a point the paper's hand schedule makes — shard_map +
  Pallas composes, global-view does not.)

On top of the unified loop every schedule gets the same stopping-criterion
subsystem: fixed iterations (the paper's benchmark protocol), relative-error
tolerance, and stall detection — adaptive stopping compiles to a
``lax.while_loop`` so distributed runs halt early without host round-trips.
The distributed schedules also share ``panel_compression="int8"``:
error-feedback int8 quantisation of the panel collectives
(repro.distributed.compression), with the residuals carried through the
same compiled loops.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import backends as _backends
from repro.core import rules as _rules
from repro.core.aunmf import NMFResult, aunmf_step_rule, init_h, init_w
from repro.obs import trace as _trace
from repro.util.compat import make_mesh

SCHEDULES = ("serial", "faun", "naive", "gspmd")
# Valid backends are whatever repro.backends.available_backends() lists
# ("dense", "pallas", "sparse" built in, plus anything registered).


# ---------------------------------------------------------------------------
# Stopping criteria
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StoppingCriterion:
    """When to halt the alternating updates.

    ``max_iters`` always bounds the loop (the paper's fixed-iteration
    protocol).  ``tol`` halts once the relative error drops below it;
    ``stall_iters`` halts after that many consecutive iterations without an
    improvement larger than ``stall_tol``.  Any combination composes.
    """

    max_iters: int = 30
    tol: float | None = None
    stall_iters: int = 0
    stall_tol: float = 1e-6

    @property
    def adaptive(self) -> bool:
        return self.tol is not None or self.stall_iters > 0


# ---------------------------------------------------------------------------
# Prepared run state — the segment API's carry
# ---------------------------------------------------------------------------

@dataclass
class RunState:
    """Device-resident state of an in-flight run, between segments.

    ``NMFSolver.prepare_state`` builds one (schedule-sharded A, factors,
    loop carry); ``run_segment`` advances it a fixed number of iterations
    in place; ``collect_result`` packs it into an ``NMFResult``.  Two
    segments of the same compiled fixed run compose bit-identically to one
    longer run — the segments re-enter the SAME jitted ``lax.scan`` body,
    so the elastic runtime (``repro.elastic``) can checkpoint at segment
    boundaries and a resumed run replays the uninterrupted trajectory
    exactly.

    ``step`` counts iterations completed so far; ``rel_history`` holds one
    host rel-error array per segment (concatenated at collect time).
    ``key`` is the PRNG key the factors were initialised from (None for
    explicit / warm-started factors) — recorded in checkpoints for
    provenance.
    """

    Arep: Any
    W: Any
    Ht: Any
    normA_sq: Any
    state: Any
    m: int
    n: int
    dtype: Any
    step: int = 0
    rel_history: list = field(default_factory=list)
    key: Any = None


# ---------------------------------------------------------------------------
# Schedules.  Each is an iteration body + a layout spec; the engine owns the
# loop, the backend owns the local products, and the rule owns the update
# computation.  The step contract is
# step(Arep, W, Ht, normA_sq, state) -> (W, Ht, sq_err, state) over (m,k) W
# and (n,k) Ht (transposed H), however Arep is represented; ``state`` is the
# rule's carry pytree (None for stateless rules), replicated on distributed
# schedules.
# ---------------------------------------------------------------------------

class _Schedule:
    """Shared schedule surface: the engine calls prepare/build_step/collect;
    lower_step uses abstract_args/arg_shardings; the run cache uses
    cache_key (must capture everything build_step's closure depends on)."""

    name: str

    def collect(self, W, Ht):
        return W, Ht.T

    def init_carry(self, m, n, dtype):
        """The step loop's carried state: the rule's carry pytree, extended
        to ``(rule_state, residuals)`` by schedules running compressed
        panel collectives (error feedback is engine state, PR 5's carry
        mechanism)."""
        return self.s.rule.init_state(m, n, self.s.k, dtype)

    def split_state(self, state):
        """(rule_state, residuals-or-None) from the loop carry."""
        if self.s.panel_compression is not None and self.name != "serial":
            return state
        return state, None

    def _factor_abstract_args(self, m, n, dtype):
        k = self.s.k
        return (jax.ShapeDtypeStruct((m, k), dtype),
                jax.ShapeDtypeStruct((n, k), dtype),
                jax.ShapeDtypeStruct((), jnp.float32))


class _GridSchedule(_Schedule):
    """Schedules laid out on a FaunGrid (paper Fig. 2 shardings)."""

    def _spec_A(self):
        return self.s.ops.spec_A(self.grid)

    @property
    def p(self) -> int:
        return self.grid.p

    def grid_shape(self) -> tuple[int, int]:
        return (self.grid.pr, self.grid.pc)

    def _state_sharding(self):
        return None

    def arg_shardings(self):
        grid = self.grid
        in_sh = (grid.sharding(self._spec_A()), grid.sharding(grid.spec_W()),
                 grid.sharding(grid.spec_Ht()), None, self._state_sharding())
        out_sh = (grid.sharding(grid.spec_W()), grid.sharding(grid.spec_Ht()),
                  None, None)
        return in_sh, out_sh


class _SerialSchedule(_Schedule):
    name = "serial"

    def __init__(self, solver: "NMFSolver"):
        self.s = solver

    @property
    def p(self) -> int:
        return 1

    def grid_shape(self) -> tuple[int, int]:
        return (1, 1)

    def cache_key(self):
        return (self.name, self.s.rule.cache_key(), self.s.ops.cache_key())

    def prepare(self, A, W0, H0):
        A = self.s.ops.prepare(A)
        return A, W0, H0.T, self.s.ops.norm_sq(A)

    def build_step(self) -> Callable:
        rule, ops = self.s.rule, self.s.ops

        def step(A, W, Ht, normA_sq, state):
            W, H, sq, state = aunmf_step_rule(
                A, W, Ht.T, rule, state, normA_sq,
                mm=ops.mm, mm_t=ops.mm_t, gram=ops.gram)
            return W, H.T, sq, state

        return step

    def abstract_args(self, m, n, dtype, nnz):
        Aabs = self.s.ops.abstract_A(m, n, dtype, nnz, 1, 1)
        return (Aabs,) + self._factor_abstract_args(m, n, dtype)

    def arg_shardings(self):
        return None


class _FaunSchedule(_GridSchedule):
    name = "faun"

    def __init__(self, solver: "NMFSolver", grid):
        from repro.core.faun import FaunGrid, make_faun_mesh
        if grid is None:
            grid = make_faun_mesh(*_square_grid(jax.device_count()))
        assert isinstance(grid, FaunGrid), grid
        self.s, self.grid = solver, grid

    def cache_key(self):
        return (self.name, self.s.rule.cache_key(), self.s.ops.cache_key(),
                self.s.panel_dtype, self.s.panel_compression, self.grid)

    def prepare(self, A, W0, H0):
        grid, ops = self.grid, self.s.ops
        A = ops.blockify(A, grid.pr, grid.pc)
        normA_sq = ops.norm_sq(A)
        Arep = jax.device_put(A, grid.sharding(self._spec_A()))
        W = jax.device_put(W0, grid.sharding(grid.spec_W()))
        Ht = jax.device_put(H0.T, grid.sharding(grid.spec_Ht()))
        return Arep, W, Ht, normA_sq

    def init_carry(self, m, n, dtype):
        state = super().init_carry(m, n, dtype)
        if self.s.panel_compression is None:
            return state
        from repro.core.faun import faun_residual_spec, init_faun_residuals
        sh = self.grid.sharding(faun_residual_spec(self.grid))
        res = jax.tree.map(lambda r: jax.device_put(r, sh),
                           init_faun_residuals(self.grid, m, n, self.s.k))
        return (state, res)

    def _state_sharding(self):
        if self.s.panel_compression is None:
            return None
        from repro.core.faun import faun_residual_spec
        return (None, self.grid.sharding(faun_residual_spec(self.grid)))

    def build_step(self) -> Callable:
        from repro.core.faun import build_faun_step
        return build_faun_step(self.grid, algo=self.s.rule, ops=self.s.ops,
                               panel_dtype=self.s.panel_dtype,
                               panel_compression=self.s.panel_compression)

    def abstract_args(self, m, n, dtype, nnz):
        grid = self.grid
        Aabs = self.s.ops.abstract_A(m, n, dtype, nnz, grid.pr, grid.pc)
        return (Aabs,) + self._factor_abstract_args(m, n, dtype)


class _NaiveSchedule(_Schedule):
    name = "naive"

    def __init__(self, solver: "NMFSolver", mesh, axis: str):
        if mesh is None:
            mesh = make_mesh((jax.device_count(),), (axis,))
        self.s, self.mesh, self.axis = solver, mesh, axis

    @property
    def p(self) -> int:
        return self.mesh.shape[self.axis]

    def grid_shape(self) -> tuple[int, int]:
        return (self.p, 1)

    def cache_key(self):
        return (self.name, self.s.rule.cache_key(), self.s.ops.cache_key(),
                self.s.panel_compression, self.mesh, self.axis)

    def _specs_A(self) -> tuple[P, P]:
        """Row- and column-blocked specs, extended over any extra
        representation dims (the BlockCOO triplet dim stays unsharded)."""
        extra = (None,) * (self.s.ops.block_leaf_ndim - 2)
        return (P(self.axis, None, *extra), P(None, self.axis, *extra))

    def prepare(self, A, W0, H0):
        ops, p, ax = self.s.ops, self.p, self.axis
        # Algorithm 2 stores A twice: row-distributed and column-distributed.
        # Canonicalise once (for sparse ops: the single dense→triplet
        # conversion) so the two layouts only repack, not reconvert.  Each
        # copy only ever runs ONE product (row copy: A·Hᵀ; column copy:
        # AᵀW), so the blockify_for hint lets representations skip the
        # unused orientation (sorted-SpMM metadata, for one).
        A = ops.pre_blockify(A)
        Arow = ops.blockify_for(A, p, 1, products=("mm",))
        Acol = ops.blockify_for(A, 1, p, products=("mm_t",))
        normA_sq = ops.norm_sq(Arow)
        sh = lambda spec: NamedSharding(self.mesh, spec)
        spec_row, spec_col = self._specs_A()
        Arow = jax.device_put(Arow, sh(spec_row))
        Acol = jax.device_put(Acol, sh(spec_col))
        W = jax.device_put(W0, sh(P(ax, None)))
        Ht = jax.device_put(H0.T, sh(P(ax, None)))
        return (Arow, Acol), W, Ht, normA_sq

    def init_carry(self, m, n, dtype):
        state = super().init_carry(m, n, dtype)
        if self.s.panel_compression is None:
            return state
        from repro.core.naive import init_naive_residuals, naive_residual_spec
        sh = NamedSharding(self.mesh, naive_residual_spec(self.axis))
        res = jax.tree.map(lambda r: jax.device_put(r, sh),
                           init_naive_residuals(self.p, m, n, self.s.k))
        return (state, res)

    def build_step(self) -> Callable:
        from repro.core.naive import build_naive_step
        base = build_naive_step(self.mesh, algo=self.s.rule, axis=self.axis,
                                ops=self.s.ops,
                                panel_compression=self.s.panel_compression)

        def step(Arep, W, Ht, normA_sq, state):
            return base(Arep[0], Arep[1], W, Ht, normA_sq, state)

        return step

    def abstract_args(self, m, n, dtype, nnz):
        ops, p = self.s.ops, self.p
        Aabs = (ops.abstract_A(m, n, dtype, nnz, p, 1),
                ops.abstract_A(m, n, dtype, nnz, 1, p))
        return (Aabs,) + self._factor_abstract_args(m, n, dtype)

    def arg_shardings(self):
        sh = lambda spec: NamedSharding(self.mesh, spec)
        ax = self.axis
        spec_row, spec_col = self._specs_A()
        state_sh = None
        if self.s.panel_compression is not None:
            from repro.core.naive import naive_residual_spec
            state_sh = (None, sh(naive_residual_spec(ax)))
        in_sh = ((sh(spec_row), sh(spec_col)), sh(P(ax, None)),
                 sh(P(ax, None)), None, state_sh)
        out_sh = (sh(P(ax, None)), sh(P(ax, None)), None, None)
        return in_sh, out_sh


class _GspmdSchedule(_GridSchedule):
    name = "gspmd"

    def __init__(self, solver: "NMFSolver", grid):
        from repro.core.faun import FaunGrid, make_faun_mesh
        if grid is None:
            grid = make_faun_mesh(*_square_grid(jax.device_count()))
        assert isinstance(grid, FaunGrid), grid
        self.s, self.grid = solver, grid
        # Global-view programs leave parallelism to the auto-partitioner,
        # which cannot split hand-written kernels — let the backend swap in
        # its partitioner-safe variant, and reject backends that have none
        # on multi-device grids (XLA would silently replicate A instead,
        # breaking the never-communicate-A invariant).
        self.gops = solver.ops.global_view_ops()
        if grid.p > 1 and not self.gops.partitionable:
            raise ValueError(
                f"gspmd × {self.gops.name!r} is single-device only: the "
                f"auto-partitioner cannot partition this backend's kernels "
                f"(use schedule='faun', which composes shard_map with them)")

    def cache_key(self):
        return (self.name, self.s.rule.cache_key(), self.gops.cache_key(),
                self.s.panel_compression, self.grid)

    def _spec_A(self):
        # Global-view sparse A is one 1×1 block with the flat triplet dim
        # sharded over ALL devices — XLA's partitioner then has no choice
        # but to keep the nonzeros local and all-reduce the k-width partial
        # products (verified in the lowered HLO by the distributed checks).
        if self.gops.block_leaf_ndim > 2:
            grid = self.grid
            return P(None, None, tuple(grid.row_axes) + (grid.col_axis,))
        return self.grid.spec_A()

    def prepare(self, A, W0, H0):
        grid, ops = self.grid, self.gops
        A = ops.prepare(A)
        normA_sq = ops.norm_sq(A)
        A = ops.pad_global(A, grid.p)
        Arep = jax.device_put(A, grid.sharding(self._spec_A()))
        W = jax.device_put(W0, grid.sharding(grid.spec_W()))
        Ht = jax.device_put(H0.T, grid.sharding(grid.spec_Ht()))
        return Arep, W, Ht, normA_sq

    def init_carry(self, m, n, dtype):
        state = super().init_carry(m, n, dtype)
        if self.s.panel_compression is None:
            return state
        from repro.core.gspmd import init_gspmd_residuals
        return (state, init_gspmd_residuals(m, n, self.s.k))

    def build_step(self) -> Callable:
        from repro.core.gspmd import gspmd_iteration
        compress = None
        if self.s.panel_compression is not None:
            from repro.distributed.compression import get_compressor
            compress = get_compressor(self.s.panel_compression)
        return functools.partial(gspmd_iteration, algo=self.s.rule,
                                 ops=self.gops, compress=compress)

    def abstract_args(self, m, n, dtype, nnz):
        Aabs = self.gops.abstract_global_A(m, n, dtype, nnz, self.grid.p)
        return (Aabs,) + self._factor_abstract_args(m, n, dtype)


def _warm_start_factors(init, m: int, n: int, k: int, dtype, rule):
    """Resolve ``fit(init=...)`` into (W0, H0): an ``NMFResult``, a
    ``repro.serve.artifact.FactorArtifact``, or a plain ``(W, H)`` pair.

    A warm start resumes the alternating updates from previously trained
    factors — the online loop's full-refactorization path, where the grown
    matrix carries the old factors (plus fold-in codes for the new rows)
    as its starting point instead of retraining cold.  W may therefore have
    MORE rows than the init produced; only the shapes against the current
    problem are validated.  Multiplicative rules (``positive_init``) get
    their warm factors floored at the dtype eps: a fold-in code with exact
    zeros would otherwise lock those entries at zero forever.
    """
    from repro.core.rules import eps_for
    W0, H0 = None, None
    if hasattr(init, "W") and hasattr(init, "H"):      # NMFResult / artifact
        W0, H0 = init.W, init.H
        valid = getattr(init, "valid_rows", None)      # sharded artifacts pad
        if valid is not None:
            W0 = jnp.asarray(W0)[:valid]
    elif isinstance(init, (tuple, list)) and len(init) == 2:
        W0, H0 = init
    else:
        raise TypeError(f"init must be an NMFResult, a FactorArtifact, or "
                        f"a (W, H) pair; got {type(init).__name__}")
    W0 = jnp.asarray(W0, dtype)
    H0 = jnp.asarray(H0, dtype)
    if W0.shape != (m, k):
        raise ValueError(f"warm-start W has shape {W0.shape}, problem "
                         f"needs {(m, k)}")
    if H0.shape != (k, n):
        raise ValueError(f"warm-start H has shape {H0.shape}, problem "
                         f"needs {(k, n)}")
    if rule.positive_init:
        eps = eps_for(dtype)
        W0 = jnp.maximum(W0, eps)
        H0 = jnp.maximum(H0, eps)
    return W0, H0


def _square_grid(p: int) -> tuple[int, int]:
    pr = max(d for d in range(1, p + 1) if p % d == 0 and d * d <= p)
    return pr, p // pr


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------

class NMFSolver:
    """One driver lifecycle for every AU-NMF schedule × local-compute backend
    × update rule.

    >>> solver = NMFSolver(k=16, algo="bpp", schedule="faun", grid=grid,
    ...                    backend="sparse", max_iters=200, tol=1e-4)
    >>> result = solver.fit(A)          # A: dense, BCOO, or BlockCOO
    >>> result = solver.fit(A2, init=result)   # resume / warm-start

    ``fit(init=...)`` warm-starts the alternating updates from previously
    trained factors — an ``NMFResult``, a ``FactorArtifact``, or a plain
    ``(W, H)`` pair — instead of the random init.  This is the online
    loop's full-refactorization path (``repro.online``): the accumulated
    matrix retrains with the stale factors (extended by fold-in codes for
    rows that arrived since) as the starting point, converging in far
    fewer iterations than a cold run.

    ``backend`` is a name registered in ``repro.backends`` ("dense",
    "pallas", "sparse", or your own via ``register_backend``) or a
    ``LocalOps`` instance.  ``algo`` is likewise open: a name registered in
    ``repro.core.rules`` ("mu", "hals", "bpp", the accelerated
    "amu"/"ahals", aliases "abpp"/"anls", or your own via
    ``register_algorithm``) or an ``UpdateRule`` instance —
    ``NMFSolver(k, algo=MyRule())`` works exactly like a custom backend
    instance.  Stateful rules' carry threads through the compiled loop and
    surfaces as ``NMFResult.extras["rule_state"]``.  The legacy entry
    points (``aunmf.fit``, ``faun.fit``, ``naive.fit``, ``gspmd.fit``) are
    thin wrappers over this class.

    ``panel_compression="int8"`` compresses the distributed schedules' panel
    collectives (Gram all-reduces, panel all-gathers and reduce-scatters)
    to int8 payloads with two-sided fp32 scales and error feedback — the
    quantisation residuals ride the engine's state carry and surface as
    ``NMFResult.extras["panel_residuals"]`` (see
    ``repro.distributed.compression``; gspmd emulates the numerics only).
    The default ``None`` keeps the exact wire format bit-identically.  It
    does not compose with ``panel_dtype`` (both rewrite the wire format).

    ``fit(profile=True)`` swaps the compiled loop for the segmented
    phase profiler (``repro.obs.phases``): per-iteration mean seconds per
    Algorithm-3 phase land in ``NMFResult.extras["phase_times"]``, joinable
    against ``predict_cost_terms`` via ``repro.obs.report``.  Pass
    ``tracer=`` a ``repro.obs.Tracer`` to also capture each segment as a
    Perfetto span.  Profiling covers the exact wire format only (refuses
    ``panel_dtype`` / ``panel_compression``).
    """

    def __init__(self, k: int, *, algo: "_rules.RuleSpec" = "bpp",
                 schedule: str = "serial",
                 backend: "_backends.BackendSpec" = "dense", grid=None,
                 mesh: Mesh | None = None, axis: str = "p",
                 max_iters: int = 30, tol: float | None = None,
                 stall_iters: int = 0, stall_tol: float = 1e-6,
                 panel_dtype=None, panel_compression: str | None = None,
                 donate: bool = False):
        if schedule not in SCHEDULES:
            raise ValueError(f"unknown schedule {schedule!r}; "
                             f"choose from {SCHEDULES}")
        self.rule = self._base_rule = _rules.get_rule(algo)  # validates early
        self.ops = _backends.get_backend(backend)
        if panel_dtype is not None:
            if schedule != "faun":
                raise ValueError("panel_dtype (low-precision panel gathers) "
                                 "is implemented by the faun schedule only")
            if not self.ops.supports_panel_dtype:
                raise ValueError(f"backend {self.ops.name!r} does not "
                                 f"support low-precision panels "
                                 f"(panel_dtype)")
        if panel_compression is not None:
            from repro.distributed.compression import COMPRESSIONS
            if panel_compression not in COMPRESSIONS:
                raise ValueError(
                    f"unknown panel_compression {panel_compression!r}; "
                    f"choose from {COMPRESSIONS} or None")
            if schedule == "serial":
                raise ValueError(
                    "panel_compression compresses the distributed panel "
                    "collectives; the serial schedule has none — use "
                    "schedule='faun' (a 1×1 grid exercises the quantisation "
                    "numerics single-device)")
            if panel_dtype is not None:
                # Both knobs rewrite the panel wire format: panel_dtype
                # ships bf16 bit patterns, panel_compression ships int8 +
                # scales.  Composing them would quantise an already-rounded
                # panel while the cost model could only account for one —
                # refuse instead of silently picking an order.
                raise ValueError(
                    "panel_dtype and panel_compression both rewrite the "
                    "panel wire format and do not compose; pick one "
                    "(int8 compression already halves bf16's panel bytes)")
        self.k, self.algo = k, self.rule.name
        self.panel_dtype, self.donate = panel_dtype, donate
        self.panel_compression = panel_compression
        self.stopping = StoppingCriterion(max_iters=max_iters, tol=tol,
                                          stall_iters=stall_iters,
                                          stall_tol=stall_tol)
        if schedule == "serial":
            self._schedule = _SerialSchedule(self)
        elif schedule == "faun":
            self._schedule = _FaunSchedule(self, grid)
        elif schedule == "naive":
            self._schedule = _NaiveSchedule(self, mesh, axis)
        else:
            self._schedule = _GspmdSchedule(self, grid)

    @property
    def schedule(self) -> str:
        return self._schedule.name

    @property
    def backend(self) -> str:
        return self.ops.name

    # -- driver lifecycle ---------------------------------------------------

    def fit(self, A, *, key: jax.Array | None = None,
            H0: jax.Array | None = None,
            W0: jax.Array | None = None, init=None,
            profile: bool = False, tracer=None) -> NMFResult:
        if profile and self.panel_compression is not None:
            raise ValueError(
                "profile=True times the uncompressed wire format; it does "
                "not compose with panel_compression (the compressed "
                "collectives fuse payload+sidecar into one phase the "
                "segmented profiler cannot attribute)")
        if profile and self.panel_dtype is not None:
            raise ValueError("profile=True does not compose with "
                             "panel_dtype (same wire-format reason as "
                             "panel_compression)")
        rs = self.prepare_state(A, key=key, H0=H0, W0=W0, init=init)
        crit = self.stopping
        if profile:
            from repro.obs import phases as _phases
            W, Ht, rels, iters_run, state, phase_times = _phases.run_profiled(
                self._schedule, rs.Arep, rs.W, rs.Ht, rs.normA_sq, rs.state,
                crit, tracer=tracer)
            W, H = self._schedule.collect(W, Ht)
            rule_state, _ = self._schedule.split_state(state)
            extras = {"schedule": self.schedule, "backend": self.backend,
                      "stopped_early": iters_run < crit.max_iters,
                      "rule_state": (None if rule_state is None
                                     else jax.device_get(rule_state)),
                      "phase_times": phase_times}
            return NMFResult(W=W, H=H, rel_errors=rels, algo=self.algo,
                             iters=iters_run, extras=extras)
        run = _cached_run(self._schedule, crit, self.donate)
        if crit.adaptive:
            W, Ht, rels, i, state = run(rs.Arep, rs.W, rs.Ht, rs.normA_sq,
                                        rs.state)
            rs.step = int(i)
            rels = rels[:rs.step]
        else:
            W, Ht, rels, state = run(rs.Arep, rs.W, rs.Ht, rs.normA_sq,
                                     rs.state, crit.max_iters)
            rs.step = crit.max_iters
        rs.W, rs.Ht, rs.state = W, Ht, state
        rs.rel_history.append(rels)
        return self.collect_result(rs)

    # -- segment API (the elastic runtime, repro.elastic) --------------------

    def prepare_state(self, A, *, key: jax.Array | None = None,
                      H0: jax.Array | None = None,
                      W0: jax.Array | None = None, init=None) -> RunState:
        """Resolve factors and lay the problem out for this solver's
        schedule, without running any iterations: the first half of
        ``fit``, exposed so segmented (checkpointed) runs share one
        prepare path.  Explicit ``W0``/``H0`` are installed untouched —
        this is the bit-identical resume path; ``init=`` warm starts go
        through the same eps-flooring as ``fit(init=...)``."""
        with _trace.span("nmf.prepare_state"):
            m, n = A.shape
            dtype = getattr(A, "dtype", jnp.float32)
            # Rules that size themselves from the problem
            # (inner_iters=None) specialise here, where the global dims are
            # first known; the prepared rule feeds the run-cache key, so
            # shape changes recompile.
            self.rule = self._base_rule.prepare_global(m, n, self.k)
            if init is not None:
                if H0 is not None or W0 is not None:
                    raise ValueError("pass either init= (a warm start) or "
                                     "explicit W0/H0, not both")
                W0, H0 = _warm_start_factors(init, m, n, self.k, dtype,
                                             self.rule)
            used_key = None
            if H0 is None or W0 is None:
                used_key = jax.random.PRNGKey(0) if key is None else key
            if H0 is None:
                H0 = init_h(used_key, n, self.k, dtype=dtype)
            if W0 is None:
                W0 = init_w(jax.random.fold_in(used_key, 1), m, self.k,
                            self.rule, dtype=dtype)
            with _trace.span("nmf.layout"):
                Arep, W, Ht, normA_sq = self._schedule.prepare(A, W0, H0)
            state0 = self._schedule.init_carry(m, n, dtype)
            return RunState(Arep=Arep, W=W, Ht=Ht, normA_sq=normA_sq,
                            state=state0, m=m, n=n, dtype=dtype, key=used_key)

    def run_segment(self, rs: RunState, iters: int) -> RunState:
        """Advance ``iters`` fixed iterations in place.  Segments re-enter
        the same cached jitted fixed run ``fit`` uses, so N segments of
        lengths summing to I are bit-identical to one ``fit`` of I
        iterations (same ``lax.scan`` body, deterministic backends) —
        the property the elastic checkpoint/restore tests assert."""
        if iters <= 0:
            return rs
        with _trace.span("nmf.segment"):
            with _trace.span("nmf.dispatch"):
                run = _cached_run(self._schedule,
                                  StoppingCriterion(max_iters=iters),
                                  self.donate)
                W, Ht, rels, state = run(rs.Arep, rs.W, rs.Ht, rs.normA_sq,
                                         rs.state, iters)
            rs.W, rs.Ht, rs.state = W, Ht, state
            rs.step += iters
            with _trace.span("nmf.fetch_errors"):
                rs.rel_history.append(jax.device_get(rels))
        return rs

    def restore_carry(self, rs: RunState, *, rule_state=None,
                      residuals=None) -> bool:
        """Install a checkpointed loop carry into a freshly prepared state,
        re-laid out for THIS solver's schedule.  The rule state is
        grid-independent (replicated) and restores onto any layout.  Panel
        residuals are grid-SHAPED: when their shapes match the current
        schedule's residual template they are re-sharded onto it; on a
        mismatch (a pr×pc remesh, or a schedule change) they are left at
        their zero re-initialisation — error feedback restarts cleanly and
        the resumed run matches the uninterrupted one within the
        compression tolerance rather than bit-exactly.  Returns False when
        that residual re-init happened, so callers can log/count it."""
        compressed = (self.panel_compression is not None
                      and self.schedule != "serial")
        t_rule, t_res = self._schedule.split_state(rs.state)
        new_rule = t_rule
        if rule_state is not None:
            if t_rule is None:
                raise ValueError(
                    f"checkpoint carries rule state but rule "
                    f"{self.algo!r} is stateless — refusing to resume a "
                    f"different algorithm's carry")
            new_rule = jax.tree.map(
                lambda t, s: jnp.asarray(s, t.dtype), t_rule, rule_state)
        residuals_kept = True
        new_res = t_res
        if compressed and residuals is not None:
            t_leaves, t_def = jax.tree_util.tree_flatten(t_res)
            s_leaves, s_def = jax.tree_util.tree_flatten(residuals)
            if (t_def == s_def and
                    all(tuple(t.shape) == tuple(s.shape)
                        for t, s in zip(t_leaves, s_leaves))):
                new_res = jax.tree.map(
                    lambda t, s: jax.device_put(jnp.asarray(s, t.dtype),
                                                t.sharding), t_res, residuals)
            else:
                residuals_kept = False
        rs.state = (new_rule, new_res) if compressed else new_rule
        return residuals_kept

    def collect_result(self, rs: RunState) -> NMFResult:
        """Pack a run state into an ``NMFResult`` (the second half of
        ``fit``): gather factors off the mesh, split the carry back into
        rule state and panel residuals, concatenate the per-segment
        rel-error history."""
        W, H = self._schedule.collect(rs.W, rs.Ht)
        rels = (jnp.concatenate([jnp.asarray(r) for r in rs.rel_history])
                if rs.rel_history else jnp.zeros((0,), jnp.float32))
        rule_state, residuals = self._schedule.split_state(rs.state)
        extras = {"schedule": self.schedule, "backend": self.backend,
                  "stopped_early": rs.step < self.stopping.max_iters,
                  "rule_state": (None if rule_state is None
                                 else jax.device_get(rule_state))}
        if residuals is not None:
            extras["panel_residuals"] = jax.device_get(residuals)
        return NMFResult(W=W, H=H, rel_errors=rels, algo=self.algo,
                         iters=rs.step, extras=extras)

    def config_fingerprint(self) -> dict:
        """JSON-able identity of this solver, recorded in every elastic
        checkpoint.  The ``k`` and ``rule`` fields are ENFORCED on resume
        (a checkpoint must never silently continue under a different rank,
        algorithm, or regularisation); the layout fields (schedule,
        backend, grid, compression) are recorded for provenance but MAY
        change across a resume — that is the remesh path."""
        ck = self._base_rule.cache_key()
        return {"k": self.k,
                "rule": f"{ck[0].__module__}.{ck[0].__qualname__}"
                        f"{ck[1:]!r}",
                "algo": self.algo,
                "schedule": self.schedule, "backend": self.backend,
                "grid": list(self._schedule.grid_shape()),
                "panel_compression": self.panel_compression,
                "panel_dtype": (None if self.panel_dtype is None
                                else str(self.panel_dtype))}

    # -- AOT lowering (dry-run / roofline) ----------------------------------

    def lower_step(self, m: int, n: int, *, dtype=jnp.float32,
                   nnz: int | None = None):
        """AOT-lower one iteration for HLO accounting, without data."""
        self.rule = self._base_rule.prepare_global(m, n, self.k)
        step = self._schedule.build_step()
        args = self._schedule.abstract_args(m, n, dtype, nnz) \
            + (self._schedule.init_carry(m, n, dtype),)
        shardings = self._schedule.arg_shardings()
        if shardings is None:
            jstep = jax.jit(step)
        else:
            in_sh, out_sh = shardings
            jstep = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
        return jstep.lower(*args)

    # -- cost-model integration ---------------------------------------------

    def predict_cost(self, m: int, n: int, *, nnz: float = 0.0,
                     bpp_iters: float = 1.0):
        """α-β-γ per-iteration cost prediction for this solver's schedule,
        with the A-product flops supplied by the backend (dense m·n·k vs
        sparse 2·nnz·k per product) and the communicated words scaled for
        ``panel_compression``."""
        from repro.core import costmodel
        pr, pc = self._schedule.grid_shape()
        rule = self._base_rule.prepare_global(m, n, self.k)
        return costmodel.schedule_cost(
            self.schedule, m, n, self.k, pr=pr, pc=pc, algo=rule,
            backend=self.ops, nnz=nnz, bpp_iters=bpp_iters,
            compression=self.panel_compression)

    def predict_cost_terms(self, m: int, n: int, *, nnz: float = 0.0,
                           bpp_iters: float = 1.0, machine=None):
        """Per-phase-group predicted seconds (gram/mm/luc/comm/error) —
        the model side of the measured-vs-predicted join against
        ``fit(profile=True)``'s ``extras["phase_times"]``; see
        ``repro.obs.report``."""
        from repro.core import costmodel
        pr, pc = self._schedule.grid_shape()
        rule = self._base_rule.prepare_global(m, n, self.k)
        return costmodel.schedule_cost_terms(
            self.schedule, m, n, self.k, pr=pr, pc=pc, algo=rule,
            backend=self.ops, nnz=nnz, bpp_iters=bpp_iters,
            compression=self.panel_compression, machine=machine)


# ---------------------------------------------------------------------------
# The two loop drivers.  Fixed-iteration runs compile to the same lax.scan
# the legacy drivers used (bit-compatible); adaptive stopping compiles to a
# lax.while_loop so early halting needs no host round-trip per iteration.
#
# The jitted closures are cached per (schedule config, criterion, donate):
# rebuilding them on every fit() would retrace and recompile each call,
# where the legacy drivers' module-level jit cached across calls.
# ---------------------------------------------------------------------------

_RUN_CACHE: dict = {}
_RUN_CACHE_MAX = 128


def _cached_run(schedule, crit: StoppingCriterion, donate: bool):
    key = (schedule.cache_key(), crit if crit.adaptive else None, donate)
    try:
        run = _RUN_CACHE.get(key)
    except TypeError:           # unhashable layout object — build uncached
        return _build_run(schedule.build_step(), crit, donate)
    if run is None:
        if len(_RUN_CACHE) >= _RUN_CACHE_MAX:
            _RUN_CACHE.clear()
        run = _build_run(schedule.build_step(), crit, donate)
        _RUN_CACHE[key] = run
    return run


def _build_run(step, crit: StoppingCriterion, donate: bool):
    return (_adaptive_run(step, crit, donate) if crit.adaptive
            else _fixed_run(step, donate))


def _fixed_run(step, donate: bool):
    @functools.partial(jax.jit, static_argnames=("iters",),
                       donate_argnums=(1, 2) if donate else ())
    def run(Arep, W, Ht, normA_sq, state, iters: int):
        def body(carry, _):
            W, Ht, state = carry
            Wn, Htn, sq, state = step(Arep, W, Ht, normA_sq, state)
            # Backends may emit fp32 from low-precision factors (fp32
            # accumulation); restore the carry dtype (no-op for fp32 runs).
            W, Ht = Wn.astype(W.dtype), Htn.astype(Ht.dtype)
            with jax.named_scope("error"):
                rel = jnp.sqrt(jnp.maximum(sq, 0.0) / normA_sq)
            return (W, Ht, state), rel

        (W, Ht, state), rels = lax.scan(body, (W, Ht, state), None,
                                        length=iters)
        return W, Ht, rels, state

    return run


def _adaptive_run(step, crit: StoppingCriterion, donate: bool):
    max_iters, tol = crit.max_iters, crit.tol
    stall_n, stall_tol = crit.stall_iters, crit.stall_tol

    @functools.partial(jax.jit, donate_argnums=(1, 2) if donate else ())
    def run(Arep, W, Ht, normA_sq, rstate):
        def cond(state):
            i, done = state[3], state[6]
            return (i < max_iters) & jnp.logical_not(done)

        def body(state):
            W, Ht, rels, i, best, stall, _, rstate = state
            Wn, Htn, sq, rstate = step(Arep, W, Ht, normA_sq, rstate)
            W, Ht = Wn.astype(W.dtype), Htn.astype(Ht.dtype)
            with jax.named_scope("error"):
                rel = jnp.sqrt(jnp.maximum(sq, 0.0) / normA_sq)
            rels = lax.dynamic_update_index_in_dim(rels, rel, i, 0)
            improved = rel < best - stall_tol
            stall = jnp.where(improved, 0, stall + 1)
            done = jnp.asarray(False)
            if tol is not None:
                done = done | (rel <= tol)
            if stall_n:
                done = done | (stall >= stall_n)
            return (W, Ht, rels, i + 1, jnp.minimum(best, rel), stall, done,
                    rstate)

        state = (W, Ht, jnp.full((max_iters,), jnp.nan, jnp.float32),
                 jnp.asarray(0, jnp.int32), jnp.asarray(jnp.inf, jnp.float32),
                 jnp.asarray(0, jnp.int32), jnp.asarray(False), rstate)
        W, Ht, rels, i, _, _, _, rstate = lax.while_loop(cond, body, state)
        return W, Ht, rels, i, rstate

    return run
