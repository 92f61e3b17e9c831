"""The ``LocalOps`` interface: local compute as a first-class backend layer.

The paper's central claim is that AU-NMF factors into a *communication
schedule* (who holds which block, which collectives move the k-width factor
panels — core/engine.py, core/faun.py, core/naive.py, core/gspmd.py) and
*purely local matrix products* (the only operations that ever touch the data
matrix A).  ``LocalOps`` is the contract for the local half:

    mm(A, B)    A @ B      — the W-step product  A·Hᵀ   (paper line 6)
    mm_t(A, B)  Aᵀ @ B     — the H-step product  (WᵀA)ᵀ (paper line 12),
                             contracting A's row dim so A is never transposed
    gram(X)     Xᵀ X       — the k×k Gram of a factor panel (lines 3/9)

plus the representation hooks a schedule needs to place A without knowing
how it is stored:

    prepare(A)             canonical single-device representation
    blockify(A, gr, gc)    representation for a gr×gc processor grid
    norm_sq(A)             ‖A‖_F² in fp32 (for relative error)
    abstract_A(...)        ShapeDtypeStruct pytree for AOT lowering
    spec_A(grid)           PartitionSpec for the blocked representation
    mm_flops(m, n, k, nnz) per-iteration flops of the two A-products,
                           so costmodel.schedule_cost stays honest per backend

Implementations live next door (dense.py / pallas.py / sparse.py) and are
looked up through a registry so projects can plug their own:

    from repro.backends import LocalOps, register_backend

    class MyOps(LocalOps):
        name = "mine"
        def mm(self, A, B): ...
        def mm_t(self, A, B): ...

    register_backend("mine", MyOps)
    NMFSolver(k, backend="mine")          # or backend=MyOps()

Every schedule in core/engine.py consumes a ``LocalOps`` instance — none of
them branch on a backend name — so a registered backend works on the whole
schedule × backend matrix for free (modulo representation support).
"""

from __future__ import annotations

import copy
from typing import Callable, Type, Union

import jax
import jax.numpy as jnp


#: The precision of every fp32 product the program runs, decided here
#: once: on TPU the default rounds fp32 operands to bf16, which moves the
#: relative error and the BPP and top-k sign tests.  On CPU it changes
#: nothing.
PRECISION = jax.lax.Precision.HIGHEST

#: Dims of ``dot``'s operands it contracts: a·b and aᵀ·b.
AB, ATB = ((1,), (0,)), ((0,), (0,))


def dot(a, b, contract=AB):
    """a·b over the ``contract`` dims (``AB``, ``ATB``, or any pair) at
    ``PRECISION``, accumulated and returned in fp32 whatever the operand
    dtypes (bf16 panels included).  Works inside Pallas kernels too."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=PRECISION,
                               preferred_element_type=jnp.float32)


#: Longest contraction one fp32 dot runs before its partial product is
#: added to an fp32 accumulator.  XLA's fp32 dot on TPU drifts low over long
#: contractions: at HIGHEST precision, AᵀW over 126,675 rows came out
#: 1.6e-5 low on average on a TPU v5e (1e-4 on the trace-trick rel error);
#: summed in 8,192-row blocks the drift was 5e-7.
ROW_BLOCK = 8192


def contract_rows(A, B, row_block: int | None = ROW_BLOCK):
    """Aᵀ·B (fp32, ``PRECISION``) for A (m, n), B (m, k), contracting the
    rows in blocks of ``row_block`` (None: one dot).  The blocks are dynamic
    slices the compiler fuses into the dot, so A is never copied; the last
    block is shifted back to end at row m, its repeated rows zeroed in B.

    For device-local operands only: under XLA's SPMD partitioner a dynamic
    slice along a sharded dimension gathers that dimension, so global-view
    programs pass ``row_block=None`` (``LocalOps.global_view_ops``)."""
    m = A.shape[0]
    if row_block is None or m <= row_block:
        return dot(A, B, ATB)

    def block(i, acc):
        start = jnp.minimum(i * row_block, m - row_block)
        a = jax.lax.dynamic_slice_in_dim(A, start, row_block, 0)
        b = jax.lax.dynamic_slice_in_dim(B, start, row_block, 0)
        fresh = start + jnp.arange(row_block) >= i * row_block
        return acc + dot(a, jnp.where(fresh[:, None], b, jnp.zeros_like(b)),
                         ATB)

    out = jnp.zeros((A.shape[1], B.shape[1]), jnp.float32)
    return jax.lax.fori_loop(0, -(-m // row_block), block, out)


class LocalOps:
    """Abstract local-compute backend.  Subclass and override the three
    products; the representation hooks default to dense behaviour."""

    #: registry key and the ``NMFSolver(...).backend`` string
    name: str = "abstract"

    #: whether low-precision factor panels (``panel_dtype=``) are supported —
    #: the backend must then accept low-precision inputs and accumulate fp32
    supports_panel_dtype: bool = True

    #: ndim of the leaves of ``blockify``'s output — schedules use this to
    #: extend their PartitionSpecs (dense (m, n) = 2; BlockCOO triplets
    #: (gr, gc, nnz) = 3)
    block_leaf_ndim: int = 2

    #: whether XLA's auto-partitioner can partition this backend's products
    #: in a global-view (gspmd) program — False for hand-written kernels
    #: (a pallas_call is opaque to the partitioner), which then work under
    #: gspmd on a single device only (shard_map schedules are unaffected)
    partitionable: bool = True

    #: row block of the fp32 contractions over a panel's rows (``gram``,
    #: and ``DenseOps.mm_t``; see ``contract_rows``); None in the
    #: global-view variant
    row_block: int | None = ROW_BLOCK

    # -- the three local products ------------------------------------------

    def mm(self, A, B):
        """A @ B for A (m, n), B (n, k) -> (m, k)."""
        raise NotImplementedError

    def mm_t(self, A, B):
        """Aᵀ @ B for A (m, n), B (m, k) -> (n, k), without transposing A."""
        raise NotImplementedError

    def gram(self, X):
        """Xᵀ X for a tall-skinny factor panel X (r, k) -> (k, k) fp32.
        Factor panels are dense on every backend (only A's storage varies).
        Rows are summed in blocks (``contract_rows``): the Gram enters
        every update and the error."""
        return contract_rows(X, X, self.row_block)

    # -- representation hooks ----------------------------------------------

    def prepare(self, A):
        """Canonicalise A for single-device (serial / global-view)
        execution.  Default: require a dense jax.Array as-is."""
        return self._require_dense(A)

    def blockify(self, A, gr: int, gc: int):
        """Representation of A for a gr × gc processor grid.  Dense arrays
        are blocked by the mesh sharding itself, so the default is a no-op."""
        return self._require_dense(A)

    def pre_blockify(self, A):
        """One-time canonicalisation before one or MORE blockify calls (the
        naive schedule blockifies twice) — convert expensive source forms
        (dense → triplets) here so each blockify only repacks."""
        return A

    def blockify_for(self, A, gr: int, gc: int,
                     products: tuple[str, ...] = ("mm", "mm_t")):
        """``blockify`` with a hint of WHICH local products will ever run on
        this copy of A — a subset of ("mm", "mm_t").  Schedules that store A
        more than once (the naive schedule keeps a row-blocked copy that
        only sees ``mm`` and a column-blocked copy that only sees ``mm_t``)
        pass the hint so representation preprocessing can skip the unused
        orientation (e.g. ``BlockCOO.sort_rows(orient=...)``).  Default:
        delegate to ``blockify`` — the hint is an optimisation, never a
        correctness requirement, so custom backends that only override
        ``blockify`` keep working on every schedule."""
        del products
        return self.blockify(A, gr, gc)

    def pad_global(self, A, p: int):
        """Pad the global-view (gspmd) representation so it shards evenly
        over p devices.  Dense arrays need nothing (XLA pads shardings)."""
        return A

    def abstract_global_A(self, m: int, n: int, dtype, nnz: int | None,
                          p: int):
        """Abstract stand-in for the global-view representation after
        ``prepare`` + ``pad_global`` (gspmd AOT lowering)."""
        return self.abstract_A(m, n, dtype, nnz, 1, 1)

    def norm_sq(self, A) -> jax.Array:
        """‖A‖_F² in fp32."""
        from repro.core.error import sq_frobenius
        return sq_frobenius(self._require_dense(A))

    def abstract_A(self, m: int, n: int, dtype, nnz: int | None,
                   gr: int, gc: int):
        """Abstract stand-in for ``blockify``'s output (AOT lowering)."""
        return jax.ShapeDtypeStruct((m, n), dtype)

    def spec_A(self, grid):
        """PartitionSpec for the blocked representation on a FaunGrid."""
        return grid.spec_A()

    def spec_rows(self, axis: str):
        """PartitionSpec sharding this backend's blocked representation over
        ONE mesh axis by rows — the serving layout (``repro.serve``: request
        batches and W shards split over a 1-D serve mesh, features/k
        replicated).  Dense blocks are (rows, features)."""
        from jax.sharding import PartitionSpec as P
        return P(axis, None)

    def cast_block(self, A, dtype):
        """Cast the local data block for low-precision panel runs."""
        return A.astype(dtype)

    def global_view_ops(self) -> "LocalOps":
        """The variant of this backend safe for global-view (gspmd)
        programs, where XLA's auto-partitioner owns the parallelism and
        cannot partition hand-written kernels, and gathers any dimension a
        dynamic slice walks along.  Default: this backend with its row
        contractions in one dot (``row_block=None``)."""
        if self.row_block is None:
            return self
        ops = copy.copy(self)
        ops.row_block = None
        return ops

    # -- cost-model hook ----------------------------------------------------

    def mm_flops(self, m: float, n: float, k: float,
                 nnz: float = 0.0) -> float:
        """Flops of the two data-matrix products per iteration (A·Hᵀ and
        AᵀW), used by ``costmodel.schedule_cost``."""
        return 4.0 * m * n * k

    def storage_words(self, m: float, n: float, nnz: float = 0.0) -> float:
        """Words needed to store A in this backend's representation."""
        return m * n

    def mm_traffic_words(self, m: float, n: float, k: float,
                         nnz: float = 0.0) -> float:
        """Memory (HBM) words moved by the two data-matrix products per
        iteration — the locality term ``costmodel`` reports alongside
        flops.  Dense default: stream A once plus read/write the k-width
        panels, per product."""
        return 2.0 * (m * n + n * k + m * k)

    def cache_key(self):
        """Hashable identity for the engine's compiled-run cache; stateful
        custom backends should extend this with their configuration.  Keyed
        on the concrete class OBJECT (not its name) so re-registering a
        redefined class under the same name invalidates cached runs."""
        return (type(self), self.name, self.row_block)

    # -- helpers ------------------------------------------------------------

    def _require_dense(self, A):
        import numpy as np
        if isinstance(A, jax.Array):
            return A
        if isinstance(A, np.ndarray):
            return jnp.asarray(A)
        raise ValueError(
            f"backend {self.name!r} needs a dense (jax or numpy) data "
            f"matrix; got {type(A).__name__} — use backend='sparse' for "
            f"BCOO/BlockCOO input")

    def __repr__(self) -> str:
        return f"{type(self).__name__}(name={self.name!r})"


def infer_backend(A) -> str:
    """Backend name implied by a data matrix's type: "dense" for anything
    dense-array-like (jax or numpy), "sparse" for BCOO/BlockCOO.  The one
    auto-detection rule the legacy fit wrappers share."""
    import numpy as np
    if isinstance(A, (jax.Array, np.ndarray)):
        return "dense"
    return "sparse"


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

BackendSpec = Union[str, LocalOps, Type[LocalOps]]

_REGISTRY: dict[str, Callable[[], LocalOps]] = {}


def register_backend(name: str, factory: Callable[[], LocalOps],
                     *, overwrite: bool = False) -> None:
    """Register a ``LocalOps`` factory (a class or zero-arg callable) under
    ``name`` so ``NMFSolver(backend=name)`` finds it."""
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} is already registered; pass "
                         f"overwrite=True to replace it")
    _REGISTRY[name] = factory


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def get_backend(spec: BackendSpec) -> LocalOps:
    """Resolve a backend name / instance / class to a ``LocalOps`` instance."""
    if isinstance(spec, LocalOps):
        return spec
    if isinstance(spec, type) and issubclass(spec, LocalOps):
        return spec()
    if isinstance(spec, str):
        try:
            factory = _REGISTRY[spec]
        except KeyError:
            raise ValueError(
                f"unknown backend {spec!r}; choose from "
                f"{available_backends()} or register_backend() your own"
            ) from None
        return factory()
    raise TypeError(f"backend must be a name, LocalOps instance, or LocalOps "
                    f"subclass; got {type(spec).__name__}")
