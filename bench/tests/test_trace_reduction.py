"""The reduction from a profiler trace to per-layer metrics, on a short
trace of the ``video-mu`` cell recorded on a TPU v5e (one 25-iteration
segment) and the compiled program it ran, kept in ``data/``."""

import glob
import os

import pytest

from harness import device, hlo, work, xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
A_SHAPE = ("f32", (126675, 13824))


def test_union_and_subtract():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.length([(0, 2), (1, 3)]) == 3
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert xplane.subtract([(0, 2), (4, 6)], []) == [(0, 2), (4, 6)]


def test_op_name_from_event():
    assert xplane.op_name("%fusion.80 = f32[8]{0} fusion(%a)") == "fusion.80"
    assert xplane.op_name("copy.3") == "copy.3"


@pytest.fixture(scope="module")
def classes():
    with open(os.path.join(DATA, "video_mu.hlo.txt")) as f:
        return hlo.classify(f.read(), [A_SHAPE])


def test_a_ops_are_the_two_products(classes):
    """The ops that read A are exactly the two products: A·Hᵀ, and Aᵀ·W
    summed over row blocks inside a loop."""
    assert classes.module == "jit_run"
    assert len(classes.a_ops) == 2
    assert set(classes.a_ops.values()) == {"fusion"}
    assert classes.collectives == {}
    assert all(name not in classes.containers for name in classes.a_ops)


@pytest.fixture(scope="module")
def reduced(classes):
    path = glob.glob(os.path.join(DATA, "*.xplane.pb"))[0]
    tr = xplane.load(path)
    assert list(tr.devices) == [0]
    return tr, xplane.reduce_device(tr.devices[0], tr.window, classes)


def test_reduction_adds_up(reduced):
    tr, d = reduced
    window = (tr.window[1] - tr.window[0]) * 1e-9
    assert 0 < d.busy <= window
    assert 0 < d.a_ops < d.busy
    assert d.collectives == 0 and d.a_custom_calls == 0
    assert d.factor_side == pytest.approx(d.busy - d.a_ops, rel=1e-9)
    gaps = sum(e - s for s, e in d.gaps) * 1e-9
    assert gaps == pytest.approx(window - d.busy, rel=1e-9)


def test_products_near_their_roofline(reduced):
    """25 iterations: the two products' device time against the least
    time two 7.0 GB reads of A take at 819 GB/s."""
    _, d = reduced
    bound = work.dense_products(126675, 13824, 50,
                                device.peaks("TPU v5 lite"))
    assert bound["bound"] == "hbm"
    share = bound["seconds"] * 25 / d.a_ops
    assert 0.5 < share <= 1.0
