import os

from repro.util import env

env.configure(host_device_count=512, platform="cpu")
# (must precede any jax import — same rule as the dry-run)

"""§Perf hillclimb driver: hypothesis → change → re-lower → measure, on the
three selected cells:

  A. llama4-maverick × train_4k   — worst useful-flops ratio in the baseline
  B. qwen2-72b × train_4k         — largest absolute collective term
  C. nmf_video_dense (paper cell) — most representative of the technique

Each experiment is a named configuration delta; metrics come from the same
trip-weighted HLO accounting as the dry-run.  Results go to
benchmarks/results/perf/<cell>_<name>.json and a markdown log.

  PYTHONPATH=src python -m benchmarks.perf_hillclimb --cell A
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import base as cb
from repro.launch import dryrun as dr
from repro.launch.mesh import make_production_mesh
from repro.roofline.hlo import collective_stats_weighted, weighted_op_costs
from repro.roofline.hw import V5E, roofline_times

RESULTS = os.path.join(os.path.dirname(__file__), "results", "perf")


def measure_lm(arch, shape_name, *, cfg_delta=None, microbatches=1,
               seq_parallel=False, name="baseline"):
    mesh = make_production_mesh()
    cfg = cb.get_config(arch)
    if cfg_delta:
        cfg = cfg.replace(**cfg_delta)
    shape = cb.SHAPES[shape_name]
    from repro.models import lm
    from repro.optim.optimizers import OptConfig
    from repro.train import steps as steps_lib

    rt = steps_lib.make_runtime(mesh, seq_parallel=seq_parallel)
    specs = lm.input_specs(cfg, shape)
    opt_cfg = OptConfig(kind=cfg.optimizer)
    step = steps_lib.make_train_step(cfg, opt_cfg, rt=rt,
                                     microbatches=microbatches)
    state_spec = steps_lib.train_state_specs(cfg, opt_cfg)
    ssh = steps_lib.state_shardings(state_spec, mesh)
    bsh = steps_lib.batch_shardings(specs, mesh)
    t0 = time.time()
    compiled = jax.jit(step, in_shardings=(ssh, bsh), out_shardings=(ssh, None),
                       donate_argnums=(0,)).lower(state_spec, specs).compile()
    t_compile = time.time() - t0
    return _metrics(compiled, name, extra={
        "arch": arch, "shape": shape_name, "compile_s": t_compile,
        "microbatches": microbatches, "seq_parallel": seq_parallel,
        "cfg_delta": {k: str(v) for k, v in (cfg_delta or {}).items()}})


def measure_nmf(m, n, k, *, pr=16, pc=16, algo="mu", panel_dtype=None,
                name="baseline"):
    from repro.core import faun as faun_lib
    from repro.util.compat import make_mesh
    mesh = make_mesh((pr, pc), ("pr", "pc"))
    grid = faun_lib.FaunGrid(mesh=mesh)
    t0 = time.time()
    compiled = faun_lib.lower_step(grid, m, n, k, algo=algo,
                                   panel_dtype=panel_dtype).compile()
    return _metrics(compiled, name, extra={
        "arch": f"nmf_m{m}_n{n}_k{k}", "grid": f"{pr}x{pc}", "algo": algo,
        "panel_dtype": str(panel_dtype), "compile_s": time.time() - t0})


def _metrics(compiled, name, extra):
    hlo = compiled.as_text()
    wc = weighted_op_costs(hlo)
    colls = collective_stats_weighted(hlo)
    ma = compiled.memory_analysis()
    mem = {"argument_bytes": ma.argument_size_in_bytes,
           "temp_bytes": ma.temp_size_in_bytes,
           "output_bytes": ma.output_size_in_bytes,
           "alias_bytes": ma.alias_size_in_bytes}
    resident = (mem["argument_bytes"] + mem["temp_bytes"]
                + mem["output_bytes"] - mem["alias_bytes"])
    roof = roofline_times(wc["dot_flops"], wc["bytes"],
                          colls.total_wire_bytes)
    rec = {"name": name, **extra,
           "flops_per_chip": wc["dot_flops"],
           "bytes_per_chip": wc["bytes"],
           "collective_bytes_per_chip": colls.total_wire_bytes,
           "collective_wire_by_op": dict(colls.wire_bytes),
           "memory": mem, "resident_bytes": resident,
           "hbm_fit": resident <= V5E.hbm_bytes,
           "roofline": roof}
    os.makedirs(RESULTS, exist_ok=True)
    fn = f"{extra.get('arch','x')}_{extra.get('shape','')}_{name}.json"
    with open(os.path.join(RESULTS, fn.replace("/", "_")), "w") as f:
        json.dump(rec, f, indent=1)
    r = rec["roofline"]
    print(f"{name:28s} flops={rec['flops_per_chip']:.3e} "
          f"bytes={rec['bytes_per_chip']:.3e} "
          f"coll={rec['collective_bytes_per_chip']:.3e} "
          f"res={resident/1e9:.1f}GB fit={rec['hbm_fit']} | "
          f"c={r['compute_s']:.3f}s m={r['memory_s']:.3f}s "
          f"x={r['collective_s']:.3f}s dom={r['dominant']}", flush=True)
    return rec


def cell_A():
    """llama4-maverick × train_4k: attack the useful-flops ratio + HBM."""
    import dataclasses
    base_moe = cb.get_config("llama4_maverick").moe
    measure_lm("llama4_maverick", "train_4k", name="A0_baseline")
    measure_lm("llama4_maverick", "train_4k", name="A1_causal_skip",
               cfg_delta={"causal_skip": True})
    measure_lm("llama4_maverick", "train_4k", name="A2_remat_dots",
               cfg_delta={"causal_skip": True, "remat_policy": "dots"})
    measure_lm("llama4_maverick", "train_4k", name="A3_microbatch4",
               cfg_delta={"causal_skip": True}, microbatches=4)
    measure_lm("llama4_maverick", "train_4k", name="A4_cap1.0",
               cfg_delta={"causal_skip": True,
                          "moe": dataclasses.replace(base_moe,
                                                     capacity_factor=1.0)},
               microbatches=4)
    measure_lm("llama4_maverick", "train_4k", name="A5_mb8",
               cfg_delta={"causal_skip": True,
                          "moe": dataclasses.replace(base_moe,
                                                     capacity_factor=1.0)},
               microbatches=8)


def cell_B():
    """qwen2-72b × train_4k: attack the collective term + HBM fit."""
    measure_lm("qwen2_72b", "train_4k", name="B0_baseline")
    measure_lm("qwen2_72b", "train_4k", name="B1_seq_parallel",
               seq_parallel=True)
    measure_lm("qwen2_72b", "train_4k", name="B2_causal_skip",
               cfg_delta={"causal_skip": True}, seq_parallel=True)
    measure_lm("qwen2_72b", "train_4k", name="B3_mb8",
               cfg_delta={"causal_skip": True}, seq_parallel=True,
               microbatches=8)
    measure_lm("qwen2_72b", "train_4k", name="B4_mb16",
               cfg_delta={"causal_skip": True}, seq_parallel=True,
               microbatches=16)


def cell_C():
    """nmf_video_dense: the paper's own workload.  C0 = paper-faithful
    (square grid); iterations are beyond-paper."""
    m, n, k = 1_013_760, 13_824, 50
    measure_nmf(m, n, k, pr=16, pc=16, name="C0_square_grid_faithful")
    # paper's own grid rule (§5.2.2): pr/pc ≈ m/n
    measure_nmf(m, n, k, pr=128, pc=2, name="C1_optimal_grid")
    measure_nmf(m, n, k, pr=256, pc=1, name="C2_1d_grid")
    measure_nmf(m, n, k, pr=128, pc=2, panel_dtype=jnp.bfloat16,
                name="C3_optgrid_bf16_panels")
    measure_nmf(m, n, k, pr=16, pc=16, panel_dtype=jnp.bfloat16,
                name="C4_square_bf16_panels")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default="all", choices=["A", "B", "C", "all"])
    args = ap.parse_args()
    if args.cell in ("A", "all"):
        cell_A()
    if args.cell in ("B", "all"):
        cell_B()
    if args.cell in ("C", "all"):
        cell_C()


if __name__ == "__main__":
    main()
