"""Reduce a profiler trace (``*.xplane.pb``) of the measured window to
per-device intervals by op class, and the host spans the harness was in.

Device ops are the events of each ``/device:TPU:<n>`` plane's ``XLA Ops``
line.  The window is the harness's ``bench.window`` host span; device
events are clipped to it.  Every quantity is a length of a union of
intervals, so overlapping ops are never counted twice.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
#: Host spans the harness records inside the window, innermost last.
HARNESS_SPANS = ("bench.window", "bench.run_segment", "bench.block")


def union(intervals) -> list:
    """Sorted, merged (start, end) pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def subtract(a, b) -> list:
    """The parts of the union of ``a`` not covered by ``b``."""
    a, b = union(a), union(b)
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


@dataclass
class Op:
    name: str
    module: str
    start: float          # ns
    end: float


@dataclass
class Trace:
    window: tuple                          # (start, end) ns, host clock
    devices: dict = field(default_factory=dict)   # device id -> [Op]
    spans: list = field(default_factory=list)     # (start, end, name)


def find_xplane(directory: str) -> str | None:
    paths = glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def op_name(event_name: str) -> str:
    """An op's HLO name from its event name, which on a TPU is the
    instruction's text (``%fusion.80 = f32[...] fusion(...)``)."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    # jit_run(<fingerprint>): the program each op ran in.
                    modules = sorted(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         ev.name.split("(", 1)[0]) for ev in line.events)
                elif line.name == "XLA Ops":
                    ops = [(ev.start_ns, ev.start_ns + ev.duration_ns,
                            op_name(ev.name)) for ev in line.events]
            devices[int(m.group(1))] = [
                Op(name=name, module=_module_at(modules, s), start=s, end=e)
                for s, e, name in ops]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HARNESS_SPANS:
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in {path}")
    window = (windows[0][0], windows[0][1])
    for dev, ops in devices.items():
        devices[dev] = [Op(o.name, o.module, max(o.start, window[0]),
                           min(o.end, window[1])) for o in ops
                        if o.end > window[0] and o.start < window[1]]
    return Trace(window=window, devices=devices, spans=spans)


def _module_at(modules, t) -> str:
    """The module whose execution interval holds time ``t``."""
    lo, hi = 0, len(modules)
    while lo < hi:
        mid = (lo + hi) // 2
        if modules[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    if lo and modules[lo - 1][0] <= t <= modules[lo - 1][1]:
        return modules[lo - 1][2]
    return ""


@dataclass
class DeviceReduction:
    """One device's window, in seconds."""
    busy: float
    a_ops: float                 # ops that read A
    a_custom_calls: float        # of those, custom calls (kernels)
    collectives: float
    collectives_exposed: float   # collective time with no other op running
    factor_side: float           # busy, less A ops and collectives
    top_ops: dict                # name -> seconds
    gaps: list                   # (start, end) idle intervals, ns


def reduce_device(ops, window, classes) -> DeviceReduction:
    """``classes``: ``hlo.Classes`` of the module the window ran."""
    mod = [o for o in ops if o.module == classes.module]
    every = [(o.start, o.end) for o in ops]
    a = [(o.start, o.end) for o in mod if o.name in classes.a_ops]
    a_cc = [(o.start, o.end) for o in mod
            if classes.a_ops.get(o.name) == "custom-call"]
    coll = [(o.start, o.end) for o in mod if o.name in classes.collectives]
    compute = [(o.start, o.end) for o in ops
               if not (o.module == classes.module
                       and o.name in classes.collectives)]
    top: dict = {}
    for o in ops:
        top[o.name] = top.get(o.name, 0.0) + (o.end - o.start) * 1e-9
    busy = union(every)
    return DeviceReduction(
        busy=length(busy) * 1e-9,
        a_ops=length(a) * 1e-9,
        a_custom_calls=length(a_cc) * 1e-9,
        collectives=length(coll) * 1e-9,
        collectives_exposed=length(subtract(coll, compute)) * 1e-9,
        factor_side=length(subtract(every, a + coll)) * 1e-9,
        top_ops=top,
        gaps=subtract([window], busy))


def innermost_span(spans, t) -> str:
    """The innermost harness span (latest start) holding time ``t``."""
    best = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or s >= best[0]):
            best = (s, name)
    return best[1] if best else "outside"
