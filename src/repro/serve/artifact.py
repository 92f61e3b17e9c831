"""Factor artifacts: the on-disk serving format for trained NMF factors.

Training ends at ``NMFResult``; serving starts here.  An artifact bundles
everything a request path needs so nothing is recomputed per query:

  * the factors ``W`` (m, k) and ``H`` (k, n);
  * the precomputed Gram ``G = HHᵀ`` (k, k, fp32) — the normal-equation
    matrix every fold-in half-update reuses (paper's ``SolveBPP(HHᵀ, ·)``),
    computed once at publish time instead of per batch;
  * the training algorithm and free-form metadata (iterations, final
    relative error, schedule/backend provenance from ``NMFResult.extras``).

On disk an artifact is a ``repro.checkpoint.checkpoint.write_payload``
directory (``arrays.npz`` + ``meta.json``, written to a tmp dir and
atomically renamed), so a crash mid-publish can never corrupt the artifact
a live server would load.

    res = NMFSolver(k, algo="bpp").fit(A)
    res.save_artifact("artifacts/topics")            # convenience wrapper
    art = FactorArtifact.load("artifacts/topics")
    proj = FoldInProjector(art)                      # repro.serve.foldin

``projection_state()`` exposes the reusable per-algorithm state (Gram +
its diagonal, both fp32) that ``repro.serve.foldin`` closes its compiled
projection over.

**Lineage:** an online loop (``repro.online``) republishes continuously;
``evolve()`` builds each successor with ``version`` bumped by one and
``parent_version`` + ``rows_absorbed`` recorded in the metadata (they
round-trip through ``save``/``load``), so staleness is observable — every
response can carry the version it was served from, and swap targets can be
rejected when they would move a server backwards.

**Sharded artifacts:** ``shard(mesh)`` places W row-sharded over a 1-D
serve mesh (``repro.serve.mesh.serve_mesh``) with H and the Gram
replicated — the serving layout every mesh-aware entry point
(``FoldInProjector(mesh=...)``, ``TopK(mesh=...)``) assumes.  shard_map
needs even shards, so W is zero-padded to a multiple of the mesh size and
the true row count is carried in ``valid_rows`` (``shape``/``save``/
``transposed`` all see the unpadded matrix; pad rows are masked out of
top-k).  ``load(path, mesh=...)`` re-shards on load, so an artifact
trained on any grid serves on any mesh.
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.backends.base import dot

FORMAT = "nmf-factor-artifact"
VERSION = 1


class ProjectionState(NamedTuple):
    """Per-artifact state a fold-in projection reuses across requests."""
    gram: jax.Array       # (k, k) fp32 — HHᵀ of the fixed factor
    diag: jax.Array       # (k,)  fp32 — its diagonal (HALS/MU init + sweeps)
    algo: str


def _gram_fp32(H: jax.Array) -> jax.Array:
    """HHᵀ with fp32 accumulation whatever H's dtype (bf16 factors serve)."""
    return dot(H, H, ((1,), (1,)))


@dataclasses.dataclass(frozen=True)
class FactorArtifact:
    """Trained factors + precomputed serving state.  Immutable.

    ``valid_rows`` is set on sharded artifacts whose W carries zero pad
    rows (to divide evenly over the mesh); everywhere the artifact is read
    as data — ``shape``, ``save``, ``transposed`` — the pad is invisible.
    """

    W: Any                # (m, k); (m_pad, k) row-sharded when mesh-placed
    H: Any                # (k, n)
    algo: str
    gram: Any             # (k, k) fp32, HHᵀ
    meta: dict = dataclasses.field(default_factory=dict)
    valid_rows: int | None = None   # true m when W is pad-extended; else None

    @property
    def k(self) -> int:
        return self.W.shape[1]

    @property
    def shape(self) -> tuple[int, int]:
        m = self.W.shape[0] if self.valid_rows is None else self.valid_rows
        return (m, self.H.shape[1])

    # -- lineage -------------------------------------------------------------
    # An online train→serve loop republishes continuously; each publish
    # records where it came from so "never serve stale factors" is a
    # checkable property: versions along a lineage are strictly increasing,
    # and every response can be stamped with the version it was computed
    # against (repro.online threads the stamp through the batcher).

    @property
    def version(self) -> int:
        """Lineage version (0 for artifacts published outside a lineage)."""
        return int(self.meta.get("version", 0))

    @property
    def parent_version(self) -> int | None:
        """Version of the artifact this one evolved from (None for roots)."""
        v = self.meta.get("parent_version")
        return None if v is None else int(v)

    @property
    def rows_absorbed(self) -> int:
        """Rows ingested between the parent artifact and this one."""
        return int(self.meta.get("rows_absorbed", 0))

    def evolve(self, W=None, H=None, *, rows_absorbed: int = 0,
               **meta) -> "FactorArtifact":
        """The next artifact in this lineage: ``version`` bumps by one and
        the parent version + rows absorbed since it are recorded.  Passing
        only ``W`` (the grown factor after fold-in extended it) reuses the
        precomputed Gram — the cheap republish of the online ingest path;
        passing ``H`` recomputes it.  Free-form ``meta`` lands in the
        child's metadata (e.g. ``refresh="blocks"``)."""
        W_new = self._unpadded_W() if W is None else jnp.asarray(W)
        if H is None:
            H_new, gram = self.H, self.gram
        else:
            H_new = jnp.asarray(H)
            gram = _gram_fp32(H_new)
        if W_new.ndim != 2 or W_new.shape[1] != H_new.shape[0]:
            raise ValueError(f"factor shapes do not compose: W "
                             f"{W_new.shape} × H {H_new.shape}")
        if H_new.shape[1] != jnp.asarray(self.H).shape[1]:
            raise ValueError(f"a lineage serves one feature space: H has "
                             f"{H_new.shape[1]} columns, parent has "
                             f"{jnp.asarray(self.H).shape[1]}")
        md = {k: v for k, v in self.meta.items()
              if k not in ("version", "parent_version", "rows_absorbed")}
        md.update(meta)
        md.update(version=self.version + 1, parent_version=self.version,
                  rows_absorbed=int(rows_absorbed))
        return FactorArtifact(W=W_new, H=H_new, algo=self.algo, gram=gram,
                              meta=md)

    def _unpadded_W(self):
        W = jnp.asarray(self.W)
        return W if self.valid_rows is None else W[:self.valid_rows]

    # -- construction -------------------------------------------------------

    @classmethod
    def from_factors(cls, W, H, *, algo: str = "bpp",
                     **meta) -> "FactorArtifact":
        W = jnp.asarray(W)
        H = jnp.asarray(H)
        if W.ndim != 2 or H.ndim != 2 or W.shape[1] != H.shape[0]:
            raise ValueError(f"factor shapes do not compose: W {W.shape} × "
                             f"H {H.shape}")
        return cls(W=W, H=H, algo=algo, gram=_gram_fp32(H), meta=dict(meta))

    @classmethod
    def from_result(cls, result, **meta) -> "FactorArtifact":
        """Build from an ``NMFResult``, keeping training provenance."""
        rels = np.asarray(result.rel_errors, np.float32)
        prov = {"iters": int(result.iters),
                "rel_error": float(rels[-1]) if rels.size else None,
                **{k: v for k, v in result.extras.items()
                   if isinstance(v, (str, int, float, bool))}}
        prov.update(meta)
        return cls.from_factors(result.W, result.H, algo=result.algo, **prov)

    # -- persistence --------------------------------------------------------

    def save(self, path: str) -> str:
        """Atomically publish to directory ``path`` (arrays.npz+meta.json).
        Sharded artifacts save their UNPADDED W — on-disk format is
        mesh-free, placement happens at load."""
        from repro.checkpoint.checkpoint import write_payload
        W = np.asarray(self._unpadded_W())
        arrays = {"W": W, "H": np.asarray(self.H),
                  "gram": np.asarray(self.gram)}
        meta = {"format": FORMAT, "version": VERSION, "algo": self.algo,
                "k": int(self.k), "shape": list(self.shape),
                "meta": self.meta}
        return write_payload(path, arrays, meta)

    @classmethod
    def load(cls, path: str, *, mesh=None) -> "FactorArtifact":
        from repro.checkpoint.checkpoint import read_payload
        arrays, meta = read_payload(path)
        if meta.get("format") != FORMAT:
            raise ValueError(f"{path} is not a {FORMAT} payload "
                             f"(format={meta.get('format')!r})")
        if meta.get("version", 0) > VERSION:
            raise ValueError(f"artifact version {meta['version']} is newer "
                             f"than this reader (supports ≤ {VERSION})")
        art = cls(W=jnp.asarray(arrays["W"]), H=jnp.asarray(arrays["H"]),
                  algo=meta["algo"], gram=jnp.asarray(arrays["gram"]),
                  meta=dict(meta.get("meta", {})))
        return art if mesh is None else art.shard(mesh)

    # -- mesh placement ------------------------------------------------------

    def shard(self, mesh) -> "FactorArtifact":
        """Place this artifact on a 1-D serve mesh: W row-sharded (zero-pad
        rows to a multiple of the mesh size; ``valid_rows`` remembers the
        true count), H and the Gram replicated.  Idempotent on the row
        data — re-sharding a sharded artifact re-pads from its valid rows."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if len(mesh.axis_names) != 1:
            raise ValueError(f"serving shards over a 1-D mesh; got axes "
                             f"{mesh.axis_names}")
        ax = mesh.axis_names[0]
        p = int(mesh.shape[ax])
        W = self._unpadded_W()
        m = W.shape[0]
        pad = (-m) % p
        if pad:
            W = jnp.pad(W, ((0, pad), (0, 0)))
        W = jax.device_put(W, NamedSharding(mesh, P(ax, None)))
        rep = NamedSharding(mesh, P())
        return dataclasses.replace(
            self,
            W=W,
            H=jax.device_put(jnp.asarray(self.H), rep),
            gram=jax.device_put(jnp.asarray(self.gram), rep),
            valid_rows=m)

    # -- serving state ------------------------------------------------------

    def projection_state(self) -> ProjectionState:
        G = jnp.asarray(self.gram, jnp.float32)
        return ProjectionState(gram=G, diag=jnp.diag(G), algo=self.algo)

    def transposed(self) -> "FactorArtifact":
        """The (Hᵀ, Wᵀ) view: fold COLUMNS of A (e.g. new documents when A
        is vocab×docs) through the same row fold-in API.  Pad rows of a
        sharded W are dropped first (they would otherwise become phantom
        columns of the transposed H)."""
        W = self._unpadded_W()
        return FactorArtifact(W=self.H.T, H=W.T, algo=self.algo,
                              gram=_gram_fp32(jnp.asarray(W.T)),
                              meta=dict(self.meta, transposed=True))
