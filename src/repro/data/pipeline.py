"""Deterministic synthetic data pipeline.

Restart/elastic-safe by construction: ``batch = f(seed, step)`` is a pure
function — no iterator state to checkpoint, and re-sharding to a different
mesh replays identical global batches.  Two LM tasks:

  * "copy":   second half of each sequence repeats the first half — a
    learnable task (induction), so end-to-end training demonstrably reduces
    loss (examples/train_lm.py).
  * "markov": order-1 Markov chain with a fixed random transition table —
    stationary cross-entropy floor, used for throughput benchmarking.

Plus the paper's NMF matrix generators (dense low-rank, sparse
Erdős–Rényi, video-like, bag-of-words-like) used by benchmarks/examples,
and the streaming ingest generator (``stream_batch``) the online
train→serve loop's tests/benchmarks replay deterministically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.backends.base import dot


# ------------------------------------------------------------------ LM data

@functools.partial(jax.jit, static_argnames=("batch", "seq", "vocab", "task"))
def lm_batch(seed: jax.Array, step: jax.Array, *, batch: int, seq: int,
             vocab: int, task: str = "copy"):
    key = jax.random.fold_in(jax.random.PRNGKey(0) if seed is None else seed,
                             step)
    if task == "copy":
        half = seq // 2
        first = jax.random.randint(key, (batch, half), 0, vocab)
        toks = jnp.concatenate([first, first], axis=1)
        if toks.shape[1] < seq + 1:
            pad = jax.random.randint(jax.random.fold_in(key, 1),
                                     (batch, seq + 1 - toks.shape[1]), 0, vocab)
            toks = jnp.concatenate([toks, pad], axis=1)
    elif task == "markov":
        k1, k2 = jax.random.split(key)
        # fixed transition table from seed only (not step)
        tkey = jax.random.PRNGKey(7)
        logits = jax.random.normal(tkey, (vocab, vocab)) * 2.0
        def gen(carry, k):
            nxt = jax.random.categorical(k, logits[carry])
            return nxt, nxt
        x0 = jax.random.randint(k1, (batch,), 0, vocab)
        _, seqs = jax.lax.scan(gen, x0, jax.random.split(k2, seq))
        toks = jnp.concatenate([x0[:, None], seqs.T], axis=1)
    else:
        toks = jax.random.randint(key, (batch, seq + 1), 0, vocab)
    return {"tokens": toks[:, :seq], "labels": toks[:, 1:seq + 1]}


def make_lm_loader(cfg, shape, *, seed: int = 0, task: str = "copy",
                   extra_specs=None):
    """Returns batch_fn(step) producing the full input dict for an arch,
    including modality stubs (deterministic from step)."""
    def batch_fn(step):
        step = jnp.asarray(step, jnp.int32)
        b = lm_batch(jax.random.PRNGKey(seed), step,
                     batch=shape.global_batch, seq=shape.seq_len,
                     vocab=cfg.vocab, task=task)
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), step)
        if cfg.is_encdec:
            b["enc_frames"] = 0.1 * jax.random.normal(
                key, (shape.global_batch, shape.seq_len, cfg.d_model),
                cfg.dtype_jnp)
        if cfg.frontend == "image_patches":
            b["img_embeds"] = 0.1 * jax.random.normal(
                key, (shape.global_batch, cfg.num_image_tokens, cfg.d_model),
                cfg.dtype_jnp)
        return b
    return batch_fn


# ----------------------------------------------------------------- NMF data

def lowrank_matrix(key, m, n, k, *, noise: float = 0.0, dtype=jnp.float32):
    """Paper §6.1.1 dense synthetic: product of two uniform factors."""
    k1, k2, k3 = jax.random.split(key, 3)
    W = jax.random.uniform(k1, (m, k), dtype)
    H = jax.random.uniform(k2, (k, n), dtype)
    A = W @ H
    if noise:
        A = A + noise * jax.random.uniform(k3, (m, n), dtype)
    return A


def _erdos_renyi_sample(key, m, n, density: float, dtype):
    """The one Erdős–Rényi sampler both storage variants draw from, so the
    same key yields the same matrix in dense and BCOO form by construction
    (tests assert the round trip).

    It samples the nonzeros' positions directly, never an (m, n) mask:
    ``round(density·m·n)`` uniform (row, col) draws on the device, of which
    repeats and values that round to zero in ``dtype`` are dropped on the
    host.  Returns row-major sorted host triplets (rows, cols, vals)."""
    import numpy as np
    count = int(round(density * m * n))
    kr, kc, kv = jax.random.split(key, 3)
    rows = np.asarray(jax.random.randint(kr, (count,), 0, m), np.int64)
    cols = np.asarray(jax.random.randint(kc, (count,), 0, n), np.int64)
    vals = np.asarray(jax.random.uniform(kv, (count,), dtype))
    _, first = np.unique(rows * n + cols, return_index=True)
    first = first[vals[first].astype(np.float32) != 0.0]
    return rows[first], cols[first], vals[first]


def erdos_renyi_matrix(key, m, n, density: float, dtype=jnp.float32):
    """Paper §6.1.1 sparse synthetic, DENSE storage (zero-masked values).

    This is the benchmark variant for comparing dense-path flops on a
    sparsity-structured matrix.  For true sparse storage — the paper's
    actual sparse workload — use :func:`erdos_renyi_bcoo`, which draws from
    the same sampler and feeds ``NMFSolver(backend="sparse")`` directly.
    """
    rows, cols, vals = _erdos_renyi_sample(key, m, n, density, dtype)
    return jnp.zeros((m, n), dtype).at[rows, cols].set(vals)


def erdos_renyi_bcoo(key, m, n, density: float, dtype=jnp.float32):
    """True sparse storage variant of :func:`erdos_renyi_matrix`: the same
    entries for the same key, as a ``jax.experimental.sparse.BCOO``, with
    no dense intermediate anywhere.  Use with
    ``NMFSolver(backend="sparse")`` (serial/gspmd 1×1 BlockCOO, or
    grid-blockified for faun/naive)."""
    import numpy as np
    from jax.experimental import sparse as jsparse
    rows, cols, vals = _erdos_renyi_sample(key, m, n, density, dtype)
    indices = jnp.asarray(np.stack([rows, cols], axis=1), dtype=jnp.int32)
    return jsparse.BCOO((jnp.asarray(vals), indices), shape=(m, n))


def stream_truth(seed: int, n: int, k: int, dtype=jnp.float32):
    """The fixed ground-truth row model a streaming ingest draws from:
    H (k, n) depends on ``seed`` only, so every step of a stream shares it
    (and an oracle retraining from scratch sees the same planted factors)."""
    return jax.random.uniform(jax.random.PRNGKey(seed), (k, n), dtype)


def stream_batch(seed: int, step: int, *, rows: int, n: int, k: int,
                 drift: float = 0.0, noise: float = 0.0,
                 dtype=jnp.float32):
    """One deterministic ingest batch of a streaming NMF workload:
    ``batch = f(seed, step)`` is pure — replaying a failing schedule
    reproduces every batch bit-identically, with no iterator state to
    checkpoint (the same design contract as :func:`lm_batch`).

    Rows are drawn from the planted model ``X_step @ H_seed``: the mixing
    codes X (rows, k) are fresh per step; H comes from
    :func:`stream_truth` and is shared by every step of the stream.
    ``drift`` > 0 moves the ground truth: step t samples rows from
    ``H + drift·t·H_alt`` (H_alt a second seed-fixed factor), the
    concept-drift regime whose accumulated error the online loop's drift
    accumulator exists to catch.  ``noise`` adds uniform measurement noise.
    """
    key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
    k1, k2 = jax.random.split(key)
    H = stream_truth(seed, n, k, dtype)
    if drift:
        H_alt = jax.random.uniform(jax.random.PRNGKey(seed + 1), (k, n),
                                   dtype)
        H = H + jnp.asarray(drift * step, dtype) * H_alt
    X = jax.random.uniform(k1, (rows, k), dtype)
    A = X @ H
    if noise:
        A = A + noise * jax.random.uniform(k2, (rows, n), dtype)
    return A


def video_like_matrix(key, m, n, *, rank: int = 20, motion: float = 0.05,
                      dtype=jnp.float32, row0: int = 0, sharding=None):
    """Static low-rank background + sparse 'moving object' outliers
    (the paper's video use-case structure): rows ``row0 .. row0+m-1`` of
    one unbounded matrix.

    Row i is a pure function of (key, i) — its background mix, mask and
    object values come from ``fold_in(key, i)`` — so any row range, e.g.
    new frames to fold in after training on the first m, is drawn without
    the rows before it.  The whole draw is one fused elementwise pass
    behind the background product: A is the only (m, n) buffer the
    compiled program allocates.  ``sharding`` places the result (a
    ``NamedSharding`` generates each device's block on that device)."""
    gen = _video_rows(m, n, rank, float(motion), jnp.dtype(dtype), sharding)
    return gen(key, jnp.asarray(row0, jnp.int32))


@functools.lru_cache(maxsize=None)
def _video_rows(m, n, rank, motion, dtype, sharding):
    def gen(key, row0):
        kh, kw, km, ko = jax.random.split(key, 4)
        H = jax.random.uniform(kh, (rank, n), dtype)
        rows = row0 + jnp.arange(m, dtype=jnp.int32)

        def per_row(k, draw):
            return jax.vmap(lambda i: draw(jax.random.fold_in(k, i)))(rows)

        W = per_row(kw, lambda k: jax.random.uniform(k, (rank,), dtype))
        mask = per_row(km, lambda k: jax.random.bernoulli(k, motion, (n,)))
        obj = per_row(ko, lambda k: jax.random.uniform(k, (n,), dtype))
        A = dot(W, H).astype(dtype)
        return jnp.where(mask, A + obj, A)

    return jax.jit(gen, out_shardings=sharding)


def bow_like_matrix(key, vocab, docs, *, topics: int = 20,
                    doc_len: int = 100, dtype=jnp.float32):
    """Bag-of-words-like: Zipfian word marginals mixed over latent topics
    (stack-exchange-shaped, nonneg sparse counts)."""
    k1, k2, k3 = jax.random.split(key, 3)
    topic_word = jax.random.dirichlet(
        k1, 0.05 * jnp.ones((vocab,)), (topics,))      # (T, V)
    doc_topic = jax.random.dirichlet(
        k2, 0.3 * jnp.ones((topics,)), (docs,))        # (D, T)
    probs = doc_topic @ topic_word                      # (D, V)
    counts = jax.random.poisson(k3, doc_len * probs).astype(dtype)
    return counts.T                                     # (V, D): words × docs
