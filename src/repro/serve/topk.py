"""Top-k retrieval over trained factors: nearest rows of W to a query's
latent code, scored in the k-dim space — single-device or sharded over a
serve mesh.

The naive score between a query's reconstruction ``x H`` and row i's
reconstruction ``w_i H`` is an n-length inner product; with the precomputed
Gram ``G = HHᵀ`` it collapses to the k-dim form

    ⟨w_i H, x H⟩ = w_i G xᵀ            (the Gram trick)

so queries are transformed ONCE (``q̃ = x G``, k² flops) and every row score
is a k-length dot — n never appears in the request path.  ``gram=None``
scores directly in latent space (plain ⟨w_i, x⟩ / cosine over codes).

W streams through fixed memory: rows are scanned in ``chunk``-row tiles
(pad tile masked to -inf) while a running (b, k) top-k set is merged per
tile with ``lax.top_k`` — millions of rows never materialise more than one
(b, chunk) score block.  ``chunk=None`` runs the measured autotuner
(``kernels/autotune``) over a candidate ladder that always includes the
hand default, so the tuned choice is never slower.  The scan compiles once
per (W shape, query bucket); reuse one ``TopK`` instance per artifact so
the jit cache stays warm.

**Sharded retrieval** (``mesh=``): W is row-sharded over a 1-D serve mesh
(``repro.serve.mesh.serve_mesh``) so artifacts beyond one device's memory
serve fine.  Each device streams ONLY its local W shard through the same
chunked scan (global row indices via the shard's row offset), producing a
per-shard (b, k) candidate set; the candidates then merge across the mesh
with a log₂(p) hypercube exchange (``lax.ppermute`` pairs at distance
1, 2, 4, …, re-top-k after each hop — every device ends with the global
top-k), falling back to one k-width ``all_gather`` + local top-k on
non-power-of-two meshes.  Only (b, k) candidate score/index sets ever
cross the wire; W shards and the (b, chunk) score tiles stay local — the
serving analog of the training schedules' k-width-panels-only invariant.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.backends.base import dot
from repro.serve.artifact import FactorArtifact
from repro.util.compat import shard_map

_NEG = -jnp.inf
_EPS = 1e-12

METRICS = ("dot", "cosine")

#: hand-picked streaming tile (rows of W scored per scan step); chunk=None
#: replaces it with the measured choice from kernels/autotune
DEFAULT_CHUNK = 4096
_CHUNK_CANDIDATES = (512, 1024, 2048, 4096, 8192, 16384)


def _scan_core(W, Wn, Q, qnorm, offset, *, k: int, metric: str, chunk: int,
               total_m: int):
    """The streaming chunk scan over ONE device's W rows.  ``offset`` is
    the shard's global row offset (traced; 0 on a single device) and
    ``total_m`` the GLOBAL valid row count, so returned indices are global
    and both chunk-padding and global tail-padding rows mask to -inf."""
    m, kl = W.shape
    b = Q.shape[0]
    pad = (-m) % chunk
    Wp = jnp.pad(W, ((0, pad), (0, 0)))
    Wnp = jnp.pad(Wn, (0, pad), constant_values=1.0)
    nchunks = Wp.shape[0] // chunk
    Wc = Wp.reshape(nchunks, chunk, kl)
    Wnc = Wnp.reshape(nchunks, chunk)
    base = jnp.arange(nchunks) * chunk

    def body(carry, tile):
        vals, idx = carry
        C, cn, start = tile
        s = dot(Q, C, ((1,), (1,)))                         # (b, chunk)
        if metric == "cosine":
            s = s / (jnp.maximum(cn, _EPS)[None, :] * qnorm[:, None])
        lidx = start + jnp.arange(chunk)                   # local row ids
        gidx = lidx + offset                               # global row ids
        s = jnp.where(((lidx < m) & (gidx < total_m))[None, :], s, _NEG)
        cand_v = jnp.concatenate([vals, s], axis=1)
        cand_i = jnp.concatenate(
            [idx, jnp.broadcast_to(gidx[None, :], (b, chunk))], axis=1)
        vals, pos = jax.lax.top_k(cand_v, k)
        idx = jnp.take_along_axis(cand_i, pos, axis=1)
        return (vals, idx), None

    init = (jnp.full((b, k), _NEG, jnp.float32),
            jnp.full((b, k), -1, jnp.int32))
    (vals, idx), _ = jax.lax.scan(body, init, (Wc, Wnc, base))
    return vals, idx


@functools.partial(jax.jit,
                   static_argnames=("k", "metric", "chunk", "total_m"))
def _topk_scan(W, Wn, Q, qnorm, offset, *, k: int, metric: str, chunk: int,
               total_m: int):
    return _scan_core(W, Wn, Q, qnorm, offset, k=k, metric=metric,
                      chunk=chunk, total_m=total_m)


def _merge_shards(vals, idx, *, k: int, axis: str, p: int, merge: str):
    """Combine per-shard (b, k) candidate sets into the global top-k on
    every device.  ``merge="tree"`` is the log₂(p) hypercube exchange
    (partners at distance 1, 2, 4, …; re-top-k per hop), ``"gather"`` one
    tiled all_gather + local top-k.  Either way only (b, ·k) candidate
    tensors cross the wire."""
    if p == 1:
        return vals, idx
    if merge == "tree":
        step = 1
        while step < p:
            perm = [(i, i ^ step) for i in range(p)]
            ov = jax.lax.ppermute(vals, axis, perm)
            oi = jax.lax.ppermute(idx, axis, perm)
            vals, pos = jax.lax.top_k(jnp.concatenate([vals, ov], axis=1), k)
            idx = jnp.take_along_axis(jnp.concatenate([idx, oi], axis=1),
                                      pos, axis=1)
            step *= 2
    else:
        av = jax.lax.all_gather(vals, axis, axis=1, tiled=True)  # (b, p·k)
        ai = jax.lax.all_gather(idx, axis, axis=1, tiled=True)
        vals, pos = jax.lax.top_k(av, k)
        idx = jnp.take_along_axis(ai, pos, axis=1)
    return vals, idx


def _resolve_merge(merge: str, p: int) -> str:
    if merge not in ("auto", "tree", "gather"):
        raise ValueError(f"merge must be 'auto', 'tree' or 'gather', got "
                         f"{merge!r}")
    if merge == "tree" and p & (p - 1):
        raise ValueError(f"the hypercube tree merge needs a power-of-two "
                         f"mesh, got {p} devices — use merge='gather'")
    if merge == "auto":
        return "tree" if p & (p - 1) == 0 else "gather"
    return merge


@functools.lru_cache(maxsize=None)
def _sharded_topk_fn(mesh, axis: str, p: int, k: int, metric: str,
                     chunk: int, total_m: int, merge: str):
    """Compiled sharded scan+merge for one (mesh, shapes) configuration.
    Cached so repeated queries reuse the jit cache (the TopK-instance
    discipline of the single-device path, enforced structurally here)."""
    from jax.sharding import PartitionSpec as P

    def body(W, Wn, Q, qnorm):
        off = jax.lax.axis_index(axis) * W.shape[0]
        vals, idx = _scan_core(W, Wn, Q, qnorm, off, k=k, metric=metric,
                               chunk=chunk, total_m=total_m)
        return _merge_shards(vals, idx, k=k, axis=axis, p=p, merge=merge)

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis, None), P(axis), P(), P()),
                   out_specs=(P(), P()))
    return jax.jit(fn)


def _serve_axis(mesh) -> tuple[str, int]:
    if len(mesh.axis_names) != 1:
        raise ValueError(f"serving shards over a 1-D mesh; got axes "
                         f"{mesh.axis_names}")
    ax = mesh.axis_names[0]
    return ax, int(mesh.shape[ax])


def _pad_rows(X, mult: int, *, value: float = 0.0):
    pad = (-X.shape[0]) % mult
    if pad == 0:
        return X
    widths = ((0, pad),) + ((0, 0),) * (X.ndim - 1)
    return jnp.pad(X, widths, constant_values=value)


def _tuned_chunk(m: int, kl: int, b: int, k: int, metric: str) -> int:
    """Measured streaming-tile search through kernels/autotune: candidates
    are the ladder clipped to m plus the hand default, so the tuned pick is
    never slower than DEFAULT_CHUNK (modulo timer noise); results persist
    in the shared autotune cache keyed on the scan's shape signature."""
    from repro.kernels import autotune as _at
    m_eff = max(m, 1)
    default = min(DEFAULT_CHUNK, m_eff)
    cands = sorted({min(c, m_eff) for c in _CHUNK_CANDIDATES} | {default})
    if len(cands) == 1:
        return cands[0]
    key = (m, kl, b, k, metric)
    cached = _at.lookup("topk_chunk", key)
    if cached is not None and len(cached) == 1 \
            and isinstance(cached[0], int) and 1 <= cached[0] <= m_eff:
        return cached[0]

    import numpy as np

    def _synth(shape, seed=0):
        return jnp.asarray(np.random.RandomState(seed)
                           .rand(*shape).astype(np.float32))

    args = functools.cache(lambda: (
        _synth((m, kl)), jnp.ones((m,), jnp.float32),
        _synth((b, kl), seed=1), jnp.ones((b,), jnp.float32),
        jnp.int32(0)))

    def run(params):
        return _topk_scan(*args(), k=k, metric=metric, chunk=params[0],
                          total_m=m)[0]

    (chosen,) = _at.tune("topk_chunk", key, [(c,) for c in cands], run)
    return chosen


@functools.partial(jax.jit, static_argnames=("use_gram",))
def _row_norms(W, G, *, use_gram: bool):
    """‖w_i H‖ per row via the Gram (√(w_i G w_iᵀ)), or latent ‖w_i‖.
    m·k² once per (W, G) — precompute and reuse across queries (TopK
    caches it); recomputing this inside the query scan would dominate the
    request path."""
    Wf = W.astype(jnp.float32)
    if use_gram:
        base = jnp.sum(dot(Wf, G) * Wf, axis=1)
    else:
        base = jnp.sum(Wf * Wf, axis=1)
    return jnp.sqrt(jnp.maximum(base, 0.0))


def topk_rows(W, queries, *, k: int = 10, gram=None, metric: str = "dot",
              chunk: int | None = DEFAULT_CHUNK, row_norms=None, mesh=None,
              merge: str = "auto", valid_rows: int | None = None):
    """Top-k rows of ``W`` (m, kl) for latent queries (b, kl).

    Returns ``(scores, indices)``, both (b, k), scores descending per query.
    ``gram`` switches on reconstruction-space scoring (pass the artifact's
    ``HHᵀ``); ``metric="cosine"`` normalises by both row and query norms in
    the same space — pass the precomputed ``row_norms`` (m,) when W is
    fixed across queries (``TopK`` does) so the m·k² norm pass leaves the
    request path.  ``chunk`` bounds resident memory at b×chunk scores;
    ``chunk=None`` autotunes it (measured, cached).

    ``mesh`` shards the scan: W (and row_norms) split row-wise over the
    1-D mesh, each device scans its shard, and the per-shard candidates
    merge across the mesh (``merge``: "tree" hypercube exchange on
    power-of-two meshes, "gather" otherwise, "auto" picks).  ``valid_rows``
    caps scoring at the first ``valid_rows`` rows (tail rows are sharding
    pad and never retrieved).
    """
    if metric not in METRICS:
        raise ValueError(f"metric must be one of {METRICS}, got {metric!r}")
    W = jnp.asarray(W)
    Q = jnp.asarray(queries)
    if Q.ndim == 1:
        Q = Q[None, :]
    if W.shape[1] != Q.shape[1]:
        raise ValueError(f"W has latent dim {W.shape[1]}, queries "
                         f"{Q.shape[1]}")
    m_valid = W.shape[0] if valid_rows is None else int(valid_rows)
    if k > m_valid:
        raise ValueError(f"k={k} exceeds the {m_valid} rows of W")
    use_gram = gram is not None
    G = (jnp.asarray(gram, jnp.float32) if use_gram
         else jnp.eye(W.shape[1], dtype=jnp.float32))
    Qf = Q.astype(jnp.float32)
    # transform queries once
    Qt = dot(Qf, G) if use_gram else Qf
    if metric == "cosine":
        if row_norms is None:
            row_norms = _row_norms(W, G, use_gram=use_gram)
        Wn = jnp.asarray(row_norms, jnp.float32)
        if Wn.shape != (W.shape[0],):
            raise ValueError(f"row_norms must be ({W.shape[0]},), got "
                             f"{Wn.shape}")
        qsq = jnp.sum(Qt * Qf, axis=1)
        qnorm = jnp.maximum(jnp.sqrt(jnp.maximum(qsq, 0.0)), _EPS)
    else:
        Wn = jnp.ones((W.shape[0],), jnp.float32)
        qnorm = jnp.ones((Q.shape[0],), jnp.float32)
    Wf32 = W.astype(jnp.float32)

    if mesh is None:
        c = chunk if chunk is not None \
            else _tuned_chunk(W.shape[0], W.shape[1], Q.shape[0], k, metric)
        c = int(min(c, max(W.shape[0], 1)))
        return _topk_scan(Wf32, Wn, Qt, qnorm, jnp.int32(0), k=k,
                          metric=metric, chunk=c, total_m=m_valid)

    ax, p = _serve_axis(mesh)
    Wp = _pad_rows(Wf32, p)
    Wnp = _pad_rows(Wn, p, value=1.0)
    mb = Wp.shape[0] // p                      # local shard rows
    c = chunk if chunk is not None \
        else _tuned_chunk(mb, W.shape[1], Q.shape[0], k, metric)
    c = int(min(c, max(mb, 1)))
    fn = _sharded_topk_fn(mesh, ax, p, k, metric, c, m_valid,
                          _resolve_merge(merge, p))
    return fn(Wp, Wnp, Qt, qnorm)


class TopK:
    """Retrieval handle bound to one artifact: ``TopK(art).query(X, k=5)``
    scores against ``art.W`` with the artifact's Gram (reconstruction
    space).  Precomputes what is fixed per artifact — for cosine, the
    (m,) row-norm vector; with ``mesh=``, the row-sharded padded W — so a
    query is purely the k-dim scores + merge (plus, sharded, the (b, k)
    candidate exchange).  ``chunk=None`` autotunes the streaming tile."""

    def __init__(self, artifact: FactorArtifact, *, metric: str = "cosine",
                 chunk: int | None = DEFAULT_CHUNK, mesh=None,
                 merge: str = "auto"):
        self.metric = metric
        self.chunk = chunk
        self.mesh = mesh
        self.merge = merge
        self.valid_rows = artifact.shape[0]
        self.gram = jnp.asarray(artifact.gram, jnp.float32)
        W = jnp.asarray(artifact.W).astype(jnp.float32)
        norms = (_row_norms(W, self.gram, use_gram=True)
                 if metric == "cosine"
                 else jnp.ones((W.shape[0],), jnp.float32))
        if mesh is not None:
            # pin the padded shards (and norms) to the serve mesh once, so
            # artifacts beyond one device's memory hold W only in shards
            from jax.sharding import NamedSharding, PartitionSpec as P
            ax, p = _serve_axis(mesh)
            W = jax.device_put(_pad_rows(W, p),
                               NamedSharding(mesh, P(ax, None)))
            norms = jax.device_put(_pad_rows(norms, p, value=1.0),
                                   NamedSharding(mesh, P(ax)))
        self.W = W
        self.row_norms = norms if metric == "cosine" else None

    def query(self, latent_codes, *, k: int = 10):
        import time as _time
        from repro.obs.metrics import default_registry as _default_registry
        from repro.obs.trace import span as _span
        t0 = _time.perf_counter()
        with _span("topk.query", k=k):
            out = topk_rows(self.W, latent_codes, k=k, gram=self.gram,
                            metric=self.metric, chunk=self.chunk,
                            row_norms=self.row_norms, mesh=self.mesh,
                            merge=self.merge, valid_rows=self.valid_rows)
        reg = _default_registry()
        reg.counter("serve_topk_queries_total",
                    help="Top-k retrieval calls").inc()
        reg.histogram("serve_topk_query_latency_s",
                      help="Top-k dispatch seconds per call").observe(
            _time.perf_counter() - t0)
        return out
