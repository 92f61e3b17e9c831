"""The program's own measurement points: phase scopes in the compiled
iteration loop, BPP's pivoting counters (tests/test_bpp.py), and the
engine's spans, which land on a JAX profiler trace while the tracer is
enabled.

The scope checks compile the loop on 4 fake host devices in one
subprocess (tests/phase_scope_checks.py)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import sparse as jsparse

from repro.backends import SparseOps
from repro.core.engine import NMFSolver
from repro.obs import trace
from repro.obs.phases import expected_phases
from repro.obs.trace import Tracer

HERE = os.path.dirname(os.path.abspath(__file__))
CASES = [f"{sched}-{algo}-{backend}"
         for sched in ("serial", "faun") for algo in ("mu", "hals", "bpp")
         for backend in ("dense", "sparse")] + [
    "naive-mu-dense", "gspmd-mu-dense"]


@pytest.fixture(scope="module")
def scope_readings():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src") + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "phase_scope_checks.py"),
         *CASES], capture_output=True, text=True, env=env, timeout=600)
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(line) for line in proc.stdout.splitlines()
            if line.startswith("{")]
    return {row["case"]: row for row in rows}


@pytest.mark.parametrize("case", CASES)
def test_compiled_loop_carries_every_phase(scope_readings, case):
    """The scopes found on the compiled loop's ops are exactly the
    schedule's phase keys, and no op of the loop body other than the
    loop's own bookkeeping lacks one."""
    row = scope_readings[case]
    assert row["phases"] == row["expected"]
    assert row["stray"] == []


def test_scope_map_knows_every_phase_key():
    """The benchmark's scope map reads the keys every schedule uses."""
    sys.path.insert(0, os.path.join(os.path.dirname(HERE), "bench"))
    try:
        from harness import scopes
    finally:
        sys.path.pop(0)
    keys = {p for s in ("serial", "faun", "naive", "gspmd")
            for p in expected_phases(s)}
    assert scopes.PHASES == keys


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``; records names."""

    def __init__(self):
        self.names = []

    def __call__(self, name, **kwargs):
        self.names.append(name)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_span_opens_a_trace_annotation_only_while_enabled(monkeypatch):
    opened = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", opened)
    tr = Tracer(enabled=False)
    with tr.span("nmf.segment"):
        pass
    assert opened.names == [] and tr.spans() == []
    tr.enable()
    with tr.span("nmf.segment"):
        with tr.span("nmf.dispatch"):
            pass
    assert opened.names == ["nmf.segment", "nmf.dispatch"]
    assert [e.name for e in tr.spans()] == ["nmf.dispatch", "nmf.segment"]


def test_enabled_span_lands_on_the_profiler_trace(tmp_path):
    """While a JAX profiler trace runs, an enabled span is an event of the
    trace's host plane."""
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("nmf.test_span"):
            jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    names = {ev.name.split("#", 1)[0]
             for plane in jax.profiler.ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "nmf.test_span" in names


@pytest.fixture
def tracer(monkeypatch):
    """A fresh enabled tracer in the process-default tracer's place."""
    tr = Tracer()
    monkeypatch.setattr(trace, "_DEFAULT", tr)
    return tr


def _inside(inner, outer) -> bool:
    return (outer.ts_us <= inner.ts_us
            and inner.ts_us + inner.dur_us <= outer.ts_us + outer.dur_us)


def test_engine_spans_nest(tracer):
    """``prepare_state`` records ``nmf.prepare_state`` around
    ``nmf.layout``; each ``run_segment`` records ``nmf.segment`` around
    ``nmf.dispatch`` and then ``nmf.fetch_errors``."""
    A = jax.random.uniform(jax.random.PRNGKey(1), (32, 24))
    solver = NMFSolver(3, algo="mu", max_iters=2)
    rs = solver.prepare_state(A)
    solver.run_segment(rs, 2)
    solver.run_segment(rs, 2)
    by = {}
    for e in tracer.spans():
        by.setdefault(e.name, []).append(e)
    assert {n: len(v) for n, v in by.items()} == {
        "nmf.prepare_state": 1, "nmf.layout": 1, "nmf.segment": 2,
        "nmf.dispatch": 2, "nmf.fetch_errors": 2}
    assert _inside(by["nmf.layout"][0], by["nmf.prepare_state"][0])
    for seg, disp, fetch in zip(by["nmf.segment"], by["nmf.dispatch"],
                                by["nmf.fetch_errors"]):
        assert _inside(disp, seg) and _inside(fetch, seg)
        assert disp.ts_us + disp.dur_us <= fetch.ts_us
    assert all(e.args == () for n in ("nmf.segment", "nmf.dispatch",
                                      "nmf.fetch_errors") for e in by[n])
    assert len(rs.rel_history) == 2


def test_sorted_sparse_layout_records_its_sort(tracer):
    key = jax.random.PRNGKey(2)
    A = jsparse.BCOO.fromdense(jnp.where(
        jax.random.bernoulli(key, 0.2, (40, 30)),
        jax.random.uniform(key, (40, 30)), 0.0))
    NMFSolver(3, algo="hals", backend=SparseOps(spmm_impl="sorted"),
              max_iters=1).prepare_state(A)
    by = {e.name: e for e in tracer.spans()}
    assert _inside(by["nmf.sort_rows"], by["nmf.layout"])
    assert _inside(by["nmf.layout"], by["nmf.prepare_state"])


def test_disabled_default_tracer_records_no_engine_span():
    assert not trace.default_tracer().enabled
    before = len(trace.default_tracer().spans())
    A = jax.random.uniform(jax.random.PRNGKey(1), (16, 12))
    solver = NMFSolver(2, algo="mu", max_iters=1)
    solver.run_segment(solver.prepare_state(A), 1)
    assert len(trace.default_tracer().spans()) == before
