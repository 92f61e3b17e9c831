"""Faults planted in the program's timed path, to show that the comparison
which decides ``correct`` catches them.  Each is a context manager that
breaks a built ``Job``'s solver until it exits:

* ``frozen``: every iteration returns the factors it was given;
* ``half_rows``: the local products use only the first half of A's rows
  (the rest left out, the sum over the kept half doubled);
* ``no_exchange``: the faun schedule's collectives are left out (each
  device keeps its own partial panels and Grams);
* ``altered``: the error each iteration reports is altered where it is
  produced (scaled by 1.01).
"""

from __future__ import annotations

import contextlib


def _rebuild(job, wrap):
    """Make ``job``'s solver build its step through ``wrap``."""
    from repro.core import engine
    sched = job.solver._schedule
    orig = sched.build_step
    sched.build_step = lambda: wrap(orig())
    engine._RUN_CACHE.clear()
    return sched


@contextlib.contextmanager
def frozen(job):
    def wrap(step):
        def broken(A, W, Ht, normA_sq, state):
            _, _, sq, state = step(A, W, Ht, normA_sq, state)
            return W, Ht, sq, state
        return broken
    sched = _rebuild(job, wrap)
    try:
        yield
    finally:
        _restore(sched)


@contextlib.contextmanager
def altered(job):
    def wrap(step):
        def broken(A, W, Ht, normA_sq, state):
            W, Ht, sq, state = step(A, W, Ht, normA_sq, state)
            return W, Ht, sq * 1.0201, state
        return broken
    sched = _rebuild(job, wrap)
    try:
        yield
    finally:
        _restore(sched)


@contextlib.contextmanager
def half_rows(job):
    import jax.numpy as jnp
    ops = job.solver.ops
    mm, mm_t = ops.mm, ops.mm_t

    def keep(X):
        rows = jnp.arange(X.shape[0])[:, None]
        return jnp.where(rows < X.shape[0] // 2, X, 0)

    ops.mm = lambda A, B: keep(mm(A, B))
    ops.mm_t = lambda A, B: 2 * mm_t(A, keep(B))
    from repro.core import engine
    engine._RUN_CACHE.clear()
    try:
        yield
    finally:
        del ops.mm, ops.mm_t
        engine._RUN_CACHE.clear()


@contextlib.contextmanager
def no_exchange(job):
    """Only for schedules with collectives: psum and psum_scatter return
    the local value (its own slice), all_gather repeats it."""
    import jax.numpy as jnp
    from jax import lax
    from repro.core import faun

    class LocalOnly:
        def __getattr__(self, name):
            return getattr(lax, name)

        @staticmethod
        def psum(x, axes):
            return x

        @staticmethod
        def psum_scatter(x, axis, *, scatter_dimension, tiled):
            n = lax.axis_size(axis)
            i = lax.axis_index(axis)
            size = x.shape[scatter_dimension] // n
            return lax.dynamic_slice_in_dim(x, i * size, size,
                                            scatter_dimension)

        @staticmethod
        def all_gather(x, axis_name, *, axis=0, tiled=False):
            n = lax.axis_size(axis_name)
            return jnp.concatenate([x] * n, axis=axis)

    saved = faun.lax
    faun.lax = LocalOnly()
    from repro.core import engine
    engine._RUN_CACHE.clear()
    try:
        yield
    finally:
        faun.lax = saved
        engine._RUN_CACHE.clear()


def _restore(sched):
    from repro.core import engine
    del sched.build_step
    engine._RUN_CACHE.clear()


FAULTS = {"frozen": frozen, "half_rows": half_rows,
          "no_exchange": no_exchange, "altered": altered}


def faults_for(cell) -> list:
    """The faults a cell can have: the exchange only where a schedule
    exchanges panels between chips."""
    names = ["frozen", "half_rows", "altered"]
    if cell.traffic["schedule"] == "faun":
        names.append("no_exchange")
    return names
