"""Phase scopes in the compiled iteration loop, run in ONE subprocess with
4 fake host devices (tests/test_tracing.py drives this).

    python tests/phase_scope_checks.py serial-mu-dense faun-bpp-sparse ...

For each ``<schedule>-<rule>-<backend>`` case given it compiles the loop
that ``NMFSolver.run_segment`` runs and prints one JSON line:
``{"case", "phases", "expected", "stray"}``: the phase keys that the
benchmark's scope map (``bench/harness/scopes.py``) finds on the loop's
ops, the schedule's ``expected_phases``, and the ops of the loop's body
that have no phase and are not the loop's own bookkeeping.
"""

from repro.util import env

env.configure(host_device_count=4, platform="cpu")   # before any jax import

import json
import os
import re
import sys

import jax
import jax.numpy as jnp
from jax.experimental import sparse as jsparse

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "bench"))

from harness import hlo, scopes  # noqa: E402

from repro.core import engine, faun  # noqa: E402
from repro.obs.phases import expected_phases  # noqa: E402
from repro.util.compat import make_mesh  # noqa: E402

#: Instructions that move or name data without computing on it.
STRUCTURAL = {"parameter", "get-tuple-element", "tuple", "constant", "copy",
              "bitcast", "broadcast", "copy-start", "copy-done"}
#: The op_name of an op the scan itself adds (its counter, its test, the
#: write of each iteration's error into its output).
LOOP_OWN = re.compile(r'op_name="jit\([^)"]*\)/while/(body|cond)/[^/"]+"')

KEY = jax.random.PRNGKey(3)
M, N, K = 64, 48, 4
A = jax.random.uniform(KEY, (M, N))
A_SP = jsparse.BCOO.fromdense(
    jnp.where(jax.random.bernoulli(KEY, 0.3, (M, N)), A, 0.0))


def compiled_loop(sched, algo, backend, iters=3) -> str:
    kw = {}
    if sched in ("faun", "gspmd"):
        kw["grid"] = faun.make_faun_mesh(2, 2)
    elif sched == "naive":
        kw["mesh"] = make_mesh((4,), ("p",))
    solver = engine.NMFSolver(K, algo=algo, schedule=sched, backend=backend,
                              max_iters=iters, **kw)
    rs = solver.prepare_state(A_SP if backend == "sparse" else A)
    run = engine._cached_run(solver._schedule,
                             engine.StoppingCriterion(max_iters=iters),
                             solver.donate)
    return run.lower(rs.Arep, rs.W, rs.Ht, rs.normA_sq, rs.state,
                     iters).compile().as_text()


def _structural_fusion(mod, ins) -> bool:
    """A fusion that only materialises constants (a broadcast of one)."""
    return ins.opcode == "fusion" and all(
        inner.opcode in STRUCTURAL
        for c in hlo._called(ins) for inner in mod.comps.get(c, ()))


def stray_ops(text: str) -> list:
    """Ops of the iteration loop's body and condition, and of everything
    they run, without a phase and not the loop's bookkeeping."""
    mod = hlo.parse(text)
    _, phases = scopes.op_phases(text)
    fused = hlo._fused_comps(mod)
    loops = [ins for ins in mod.comps[mod.entry] if ins.opcode == "while"]
    todo = [c for ins in loops for c in hlo._called(ins)]
    seen = set()
    stray = []
    while todo:
        comp = todo.pop()
        if comp in seen or comp in fused:
            continue
        seen.add(comp)
        for ins in mod.comps.get(comp, ()):
            todo += hlo._called(ins)
            if (ins.name in phases or ins.opcode in STRUCTURAL
                    or LOOP_OWN.search(ins.attrs)
                    or _structural_fusion(mod, ins)):
                continue
            stray.append(f"{ins.name} = {ins.opcode} {ins.attrs[:120]}")
    return stray


def main(cases) -> int:
    for case in cases:
        sched, algo, backend = case.split("-")
        text = compiled_loop(sched, algo, backend)
        _, phases = scopes.op_phases(text)
        print(json.dumps({"case": case,
                          "phases": sorted(set(phases.values())),
                          "expected": sorted(expected_phases(sched)),
                          "stray": stray_ops(text)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
