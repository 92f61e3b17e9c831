"""Compile the Pallas kernels for a TPU v5e at the widths ``chip_smoke.py``
runs, without a chip: the TPU compiler is installed and compiles for a
described topology.  Interpret mode (every other kernel test) cannot see
what Mosaic refuses — unaligned tiles, more VMEM than the scoped limit — so
these guard the compiled path at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library at a time, and every test worker
imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

#: v5e HBM per chip (Google Cloud TPU v5e documentation).
HBM_BYTES = 16 * 1024 ** 3

# chip_smoke.py's shapes: one chip's share of the paper's video matrix
# (1,013,400 × 13,824 on an 8 × 1 grid) and of its sparse synthetic
# matrix (172,800 × 115,200 at density 1e-3 on a 4 × 4 grid), k = 50.
M_DENSE, N_DENSE = 126_675, 13_824
M_SPARSE, N_SPARSE = 43_200, 28_800
NNZ = 1_244_160
K = 50


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def on_tpu(monkeypatch):
    """The wrappers pick interpret mode from ``jax.default_backend()``,
    which here is the CPU; the compile targets the described chip."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    return compiled.as_text(), total


def _check(text, total):
    assert "tpu_custom_call" in text
    assert total <= HBM_BYTES, f"{total / 2**30:.2f} GiB > 16 GiB"


F32, I32 = jnp.float32, jnp.int32


def test_ts_matmul_compiles(one_chip, on_tpu):
    text, total = _compile(ops.ts_matmul, one_chip,
                           ((M_DENSE, N_DENSE), F32), ((N_DENSE, K), F32))
    _check(text, total)
    # A is read in place: nothing near its 7 GB is allocated beside it.
    assert total < M_DENSE * N_DENSE * 4 * 1.1


def test_ts_matmul_t_compiles(one_chip, on_tpu):
    text, total = _compile(ops.ts_matmul_t, one_chip,
                           ((M_DENSE, N_DENSE), F32), ((M_DENSE, K), F32))
    _check(text, total)
    assert total < M_DENSE * N_DENSE * 4 * 1.1


@pytest.mark.parametrize("rows", [M_DENSE, N_DENSE])
def test_gram_compiles(one_chip, on_tpu, rows):
    _check(*_compile(ops.gram, one_chip, ((rows, K), F32)))


@pytest.mark.parametrize("m_out,n_in", [(M_SPARSE, N_SPARSE),
                                        (N_SPARSE, M_SPARSE)])
def test_spmm_compiles(one_chip, on_tpu, m_out, n_in):
    """The unsorted kernel keeps all of B and of the output in VMEM: both
    orientations of the sparse phase's block (mm and mm_t)."""
    fn = lambda v, r, c, b: ops.spmm(v, r, c, b, m_out)
    _check(*_compile(fn, one_chip, ((NNZ,), F32), ((NNZ,), I32),
                     ((NNZ,), I32), ((n_in, K), F32)))


def _sorted(m_out, n_in, units):
    """The sorted kernel on the sparse phase's block as ``sort_rows`` packs
    it at the compiled align: 64-row units (``unit_rows_for``), ``units``
    align-sized chunks in all."""
    align, unit = 1024, 64
    L = units * align
    fn = lambda v, r, c, o, t, ok, b: ops.spmm_sorted(
        v, r, c, o, t, ok, b, m_out, align=align, unit_rows=unit)
    return fn, ((L,), F32), ((L,), I32), ((L,), I32), ((m_out + 1,), I32), \
        ((units,), I32), ((units,), I32), ((n_in, K), F32)


def test_spmm_sorted_compiles(one_chip, on_tpu):
    # 675 units of 64 rows, ~1,843 nonzeros each: two 1,024 chunks.
    fn, *shapes = _sorted(M_SPARSE, N_SPARSE, 2 * M_SPARSE // 64)
    _check(*_compile(fn, one_chip, *shapes))


def test_spmm_sorted_t_compiles(one_chip, on_tpu):
    # The transposed copy: 450 units of 64 columns, ~2,765 nonzeros each.
    fn, *shapes = _sorted(N_SPARSE, M_SPARSE, 3 * N_SPARSE // 64)
    _check(*_compile(fn, one_chip, *shapes))
