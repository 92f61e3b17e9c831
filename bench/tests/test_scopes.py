"""The per-layer readers this benchmark gained with the program's phase
scopes and BPP's pivoting counters (``harness/scopes.py``), and a pin of
the readers that were there before them, on the ``video-mu`` recording of
a program without scopes (``data/video_mu.*``)."""

import os
import shutil

import pytest

from harness import cell as cellmod
from harness import device, hlo, scopes, spec, work, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
A_SHAPE = ("f32", (126675, 13824))
NEW_READERS = ("luc_ms_per_iter", "gram_ms_per_iter", "error_ms_per_iter",
               "bpp_pivot_rounds_per_iter", "bpp_active_row_pct")


def trace_dir(tmp, name):
    """A trace directory as ``cell.run`` leaves it, from a recording."""
    d = os.path.join(tmp, name)
    os.makedirs(os.path.join(d, "plugins", "profile", "0"))
    shutil.copy(os.path.join(DATA, f"{name}.xplane.pb"),
                os.path.join(d, "plugins", "profile", "0"))
    shutil.copy(os.path.join(DATA, f"{name}.hlo.txt"),
                os.path.join(d, "program.hlo.txt"))
    return d


def reduction(d, iters=25):
    with open(os.path.join(d, "program.hlo.txt")) as f:
        classes = hlo.classify(f.read(), [A_SHAPE])
    bound = work.dense_products(126675, 13824, 50,
                                device.peaks("TPU v5 lite"))
    t = cellmod.reduce_trace(d, classes, iters, bound)
    t["counters"] = {}
    return t


@pytest.fixture(scope="module")
def unscoped_run(tmp_path_factory):
    d = trace_dir(str(tmp_path_factory.mktemp("trace")), "video_mu")
    return d, reduction(d)


def test_existing_readers_read_what_they_read_before(unscoped_run):
    """The five readers the benchmark had read the same values on the
    recording as before this benchmark's additions."""
    _, t = unscoped_run
    got = {name: spec.metric_reader(name)(t) for name in (
        "device_idle_pct", "dense_products_roofline", "spmm_roofline",
        "factor_side_ms_per_iter", "bpp_clamped_rows_per_iter")}
    assert got == {
        "device_idle_pct": pytest.approx(0.5731471157721879, rel=1e-12),
        "dense_products_roofline": pytest.approx(84.498925191357, rel=1e-12),
        "spmm_roofline": None,
        "factor_side_ms_per_iter": pytest.approx(0.4697782800000001,
                                                 rel=1e-12),
        "bpp_clamped_rows_per_iter": None}


def test_new_readers_are_silent_on_a_program_without_scopes(
        unscoped_run, monkeypatch):
    d, t = unscoped_run
    monkeypatch.setattr(scopes, "TRACE_DIR", d)
    assert scopes.op_phases(open(os.path.join(d, "program.hlo.txt")).read()
                            )[1] == {}
    assert {name: spec.metric_reader(name)(t) for name in NEW_READERS} == \
        dict.fromkeys(NEW_READERS)


def test_readers_ignore_a_trace_of_another_run(unscoped_run, monkeypatch):
    d, t = unscoped_run
    monkeypatch.setattr(scopes, "TRACE_DIR", d)
    assert scopes.for_run({**t, "window_s": t["window_s"] * 2}) is None


def test_pivot_readers():
    t = {"iters": 4, "counters": {"pivot_rounds": 2000,
                                  "pivot_row_rounds": 2000 * 8192 // 4}}
    read = spec.metric_reader
    assert read("bpp_pivot_rounds_per_iter")(t) == 500
    assert read("bpp_active_row_pct")(t) == pytest.approx(25.0)
    t["counters"]["pivot_rounds"] = 0
    assert read("bpp_active_row_pct")(t) is None


def _op(name, start, end, module="jit_run"):
    return xplane.Op(name=name, module=module, start=start, end=end)


def test_attribution_adds_up_to_busy():
    """Each instant goes to the op that started last among those running:
    a loop's own time where none of its ops runs, an op of another module
    or without a phase to ``unscoped``."""
    ops = [_op("loop", 0, 100), _op("a", 10, 30), _op("b", 25, 50),
           _op("c", 60, 70), _op("copy", 70, 80), _op("x", 90, 120, "other"),
           _op("a", 200, 210)]
    phases = {"loop": "luc_w", "a": "mm_w", "b": "luc_w", "c": "error"}
    got = scopes.attribute(ops, "jit_run", phases)
    ns = {k: v * 1e9 for k, v in got.items()}
    assert ns == pytest.approx({"luc_w": 10 + 25 + 10 + 10,
                                "mm_w": 15 + 10, "error": 10,
                                "unscoped": 10 + 30})
    busy = xplane.length([(o.start, o.end) for o in ops]) * 1e-9
    assert sum(got.values()) == pytest.approx(busy, rel=1e-12)


HLO = """HloModule jit_run, is_scheduled=true

%fused.1 (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %m1 = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(run)/while/body/luc_w/mul"}
  %m2 = f32[4]{0} add(%m1, %p0), metadata={op_name="jit(run)/while/body/luc_w/add"}
  ROOT %m3 = f32[4]{0} add(%m2, %p0), metadata={op_name="jit(run)/while/body/error/add"}
}

%inner_body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %v = f32[4]{0} get-tuple-element(%t), index=1
  %copy.1 = f32[4]{0} copy(%v)
  ROOT %r = (s32[], f32[4]{0}) tuple(%i, %copy.1)
}

%body (s: (s32[], f32[4])) -> (s32[], f32[4]) {
  %s = (s32[], f32[4]{0}) parameter(0)
  %x = f32[4]{0} get-tuple-element(%s), index=1
  %fusion.1 = f32[4]{0} fusion(%x), kind=kLoop, calls=%fused.1, metadata={op_name="jit(run)/while/body/dynamic_update_slice"}
  %dot.1 = f32[4]{0} multiply(%fusion.1, %x), metadata={op_name="jit(run)/while/body/shard_map/mm_w/dot_general"}
  %all-reduce.1 = f32[4]{0} all-reduce(%dot.1), replica_groups={{0,1}}, to_apply=%fused.1
  %while.1 = (s32[], f32[4]{0}) while(%s), condition=%cond, body=%inner_body, metadata={op_name="jit(run)/while/body/luc_h/while"}
  %copy.2 = f32[4]{0} copy(%x)
  %copy.4 = f32[4]{0} copy(%dot.1)
  %copy.5 = f32[4]{0} copy(%x)
  %add.9 = f32[4]{0} add(%copy.5, %copy.4), metadata={op_name="jit(run)/while/body/error/add"}
  ROOT %out = (s32[], f32[4]{0}) tuple(%while.1, %all-reduce.1)
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %while.0 = (s32[], f32[4]{0}) while(%a), condition=%cond, body=%body, metadata={op_name="jit(run)/while"}
  ROOT %copy.3 = f32[4]{0} copy(%a)
}
"""


def test_op_phases_rules():
    """An op's own scope; else a fusion's majority phase, a layout
    copy's producer's or reader's phase, the phase of the loop it runs
    in, or, for an all-reduce of a product's output, the reduce-scatter
    it was rewritten from; else no phase."""
    module, phases = scopes.op_phases(HLO)
    assert module == "jit_run"
    assert phases["fusion.1"] == "luc_w"          # 2 of its 3 carry luc_w
    assert phases["dot.1"] == "mm_w"
    assert phases["all-reduce.1"] == "reduce_scatter_w"
    assert phases["while.1"] == "luc_h"
    assert phases["copy.1"] == "luc_h"            # inside the luc_h loop
    assert phases["copy.4"] == "mm_w"             # its operand's phase
    assert phases["copy.5"] == "error"            # its reader's phase
    for name in ("copy.2", "while.0", "copy.3", "x"):
        assert name not in phases
    assert "m1" not in phases                     # fused: no event of its own
