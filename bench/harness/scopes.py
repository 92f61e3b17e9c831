"""Device time per phase of an iteration, from the program's own phase
scopes.

The program runs each phase of an iteration under ``jax.named_scope``
with one of the keys in ``PHASES`` (``repro.obs.phases.expected_phases``
lists them per schedule); the compiled HLO carries the scope in each
instruction's ``metadata={op_name="jit(run)/while/body/.../luc_w/..."}``.
An op's phase is the first component of that path that is a phase key.
A fusion whose own metadata names no phase takes the phase that most of
the instructions of its fused computation (and of what those call) carry;
a layout copy without one, the phase of the op that makes its operand or
else of one that reads it; any other op without one, the phase of the
loop, branch or call it runs in; an all-reduce without one that reads a local product's output
(``mm_w``, ``mm_h``), the reduce-scatter the compiler rewrote into it
(``reduce_scatter_w``, ``reduce_scatter_h``).  The rest counts as
``unscoped``: the iteration loop's own
bookkeeping, copies the compiler added between phases, and every op of a
program without the scopes.

Device time is attributed instant by instant: at each instant the op
that started last among those running takes it (a loop's own time goes
to the loop only where none of its ops runs), so the phases' seconds and
the unscoped seconds add up to the busy time exactly.

A per-layer reader finds the traced window where ``harness/cell.py``
left it, ``bench/.trace/`` (``program.hlo.txt`` and the profiler's
``*.xplane.pb``), and checks that it is the window of the run it reads.
"""

from __future__ import annotations

import functools
import heapq
import os
import re
from collections import Counter

from . import hlo, spec, xplane

PHASES = frozenset((
    "gram_w", "mm_w", "luc_w", "gram_h", "mm_h", "luc_h", "error",
    "allreduce_gram_w", "allgather_h", "reduce_scatter_w",
    "allreduce_gram_h", "allgather_w", "reduce_scatter_h"))
UNSCOPED = "unscoped"
TRACE_DIR = os.path.join(spec.BENCH, ".trace")

_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def phase_of_path(op_name: str) -> str | None:
    for part in op_name.split("/"):
        if part in PHASES:
            return part
    return None


def _own_phase(ins: hlo.Instr) -> str | None:
    m = _OP_NAME.search(ins.attrs)
    return phase_of_path(m.group(1)) if m else None


def op_phases(text: str) -> tuple[str, dict]:
    """(module name, {instruction name: phase}) for the instructions that
    run as ops of their own (those outside fused computations); an
    instruction with no phase is left out.  Without a phase of its own, a
    fusion takes the phase most of its fused instructions carry, a copy
    the phase of its operand's or else its reader's op, and any op the
    phase of the loop, branch or call it runs in."""
    mod = hlo.parse(text)
    fused = hlo._fused_comps(mod)
    caller = {}
    for comp, instrs in mod.comps.items():
        if comp in fused:
            continue
        for ins in instrs:
            if ins.opcode != "fusion":
                for called in hlo._called(ins):
                    caller.setdefault(called, ins)

    @functools.lru_cache(maxsize=None)
    def counts(comp: str) -> Counter:
        c: Counter = Counter()
        for ins in mod.comps.get(comp, ()):
            p = _own_phase(ins)
            if p is not None:
                c[p] += 1
            for called in hlo._called(ins):
                if called != comp:
                    c.update(counts(called))
        return c

    @functools.lru_cache(maxsize=None)
    def enclosing(comp: str) -> str | None:
        ins = caller.get(comp)
        return None if ins is None else phase(ins)

    @functools.lru_cache(maxsize=None)
    def _phase(comp: str, name: str) -> str | None:
        ins = by_name[(comp, name)]
        p = _own_phase(ins)
        if p is None and ins.opcode == "fusion":
            inner: Counter = Counter()
            for called in hlo._called(ins):
                inner.update(counts(called))
            if inner:
                p = inner.most_common(1)[0][0]
        if p is None and ins.opcode == "all-reduce":
            p = _rewritten_reduce_scatter(ins)
        if p is None and ins.opcode == "copy":
            p = _layout_copy(ins)
        return p if p is not None else enclosing(comp)

    def phase(ins: hlo.Instr) -> str | None:
        return _phase(ins.comp, ins.name)

    def _rewritten_reduce_scatter(ins: hlo.Instr) -> str | None:
        # The TPU compiler may rewrite a reduce-scatter as an all-reduce
        # and a slice, and the all-reduce then carries no metadata.  The
        # only collective a schedule applies to the output of a local
        # product (``mm_w``/``mm_h``) is its reduce-scatter.
        src = by_name.get((ins.comp, ins.operands[0])) \
            if len(ins.operands) == 1 else None
        while src is not None and src.opcode in ("bitcast", "copy") \
                and _own_phase(src) is None and src.operands:
            src = by_name.get((src.comp, src.operands[0]))
        side = None if src is None else phase(src)
        if side in ("mm_w", "mm_h"):
            return "reduce_scatter_" + side[-1]
        return None

    def _layout_copy(ins: hlo.Instr) -> str | None:
        # A copy the compiler adds to change a layout between two ops
        # carries no metadata; it serves the op that makes its operand,
        # or else the op that reads it.
        src = by_name.get((ins.comp, ins.operands[0])) \
            if ins.operands else None
        if src is not None and src.opcode != "copy" and _own_phase(src):
            return phase(src)
        for user in users.get((ins.comp, ins.name), ()):
            if user.opcode != "copy" and _own_phase(user):
                return phase(user)
        return None

    by_name = {(ins.comp, ins.name): ins for instrs in mod.comps.values()
               for ins in instrs}
    users: dict = {}
    for instrs in mod.comps.values():
        for ins in instrs:
            for operand in ins.operands:
                users.setdefault((ins.comp, operand), []).append(ins)
    out = {}
    for comp, instrs in mod.comps.items():
        if comp in fused:
            continue
        for ins in instrs:
            p = phase(ins)
            if p is not None:
                out[ins.name] = p
    return mod.name, out


def attribute(ops, module: str, phases: dict) -> dict:
    """Seconds of the union of ``ops`` (``xplane.Op``) by phase, with
    ``UNSCOPED`` for ops of other modules and ops without a phase; at each
    instant the op that started last among those running takes it."""
    label = [phases.get(o.name, UNSCOPED) if o.module == module
             else UNSCOPED for o in ops]
    order = sorted(range(len(ops)), key=lambda i: ops[i].start)
    bounds = sorted({t for o in ops for t in (o.start, o.end)})
    out: dict = {}
    running: list = []          # (-start, -index, end): latest start first
    nxt = 0
    for lo, hi in zip(bounds, bounds[1:]):
        while nxt < len(order) and ops[order[nxt]].start <= lo:
            i = order[nxt]
            heapq.heappush(running, (-ops[i].start, -i, ops[i].end))
            nxt += 1
        # Ended ops leave as they reach the top; those below it wait.
        while running and running[0][2] <= lo:
            heapq.heappop(running)
        if running:
            name = label[-running[0][1]]
            out[name] = out.get(name, 0.0) + (hi - lo) * 1e-9
    return out


def reduce_window(trace_dir: str, iters: int) -> dict:
    """Per-phase device seconds of the traced window in ``trace_dir``,
    the mean over its devices, with the busy time."""
    with open(os.path.join(trace_dir, "program.hlo.txt")) as f:
        module, phases = op_phases(f.read())
    path = xplane.find_xplane(trace_dir)
    tr = xplane.load(path)
    n = max(len(tr.devices), 1)
    phase_s: dict = {}
    busy = 0.0
    for ops in tr.devices.values():
        for name, s in attribute(ops, module, phases).items():
            phase_s[name] = phase_s.get(name, 0.0) + s / n
        busy += xplane.length([(o.start, o.end) for o in ops]) * 1e-9 / n
    unscoped = phase_s.pop(UNSCOPED, 0.0)
    return {"iters": iters, "window_s": (tr.window[1] - tr.window[0]) * 1e-9,
            "busy_s": busy, "phase_s": phase_s, "unscoped_s": unscoped,
            "scoped_ops": len(phases)}


@functools.lru_cache(maxsize=2)
def _cached(trace_dir: str, stamp: float, iters: int) -> dict:
    return reduce_window(trace_dir, iters)


def for_run(t: dict, trace_dir: str | None = None) -> dict | None:
    """``reduce_window`` of the run whose reduction is ``t`` (the dict a
    per-layer reader gets), from ``trace_dir`` (``TRACE_DIR`` by default),
    or None where the trace there is not that run's or the program has no
    phase scopes."""
    trace_dir = trace_dir or TRACE_DIR
    path = xplane.find_xplane(trace_dir)
    if path is None or t.get("iters", 0) <= 0:
        return None
    got = _cached(trace_dir, os.path.getmtime(path), t["iters"])
    if abs(got["window_s"] - t["window_s"]) > 1e-6 * max(t["window_s"], 1):
        return None
    if not got["scoped_ops"]:
        return None
    return got


def ms_per_iter(t: dict, prefixes: tuple) -> float | None:
    """Device ms per iteration of the phases whose keys start with one of
    ``prefixes``; None where the run's trace has no phase scopes."""
    got = for_run(t)
    if got is None:
        return None
    s = sum(v for name, v in got["phase_s"].items()
            if name.startswith(prefixes))
    return 1e3 * s / got["iters"]
