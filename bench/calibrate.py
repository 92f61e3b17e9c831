#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell at
its own size, in one process:

* the program, on every seed given: the numbers ``run.py`` compares, from
  the first segment of the timed path against the plain reference;
* the control, on the first ``--control`` seeds: the reference itself
  at the precision below the configuration's (``"high"``, three bf16
  passes), put in the program's place;
* each fault the cell can have (``harness/faults.py``), planted in the
  program's timed path, on the first ``--faults`` seeds;
* with ``--witness``, the reference again at the same precision with its
  sums taken in another order (half the row blocks and nonzero chunks),
  on the control's seeds: how far the reference itself moves with
  rounding.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \\
        [--control 3] [--faults 3]

Prints one JSON line per seed and reading, then the largest program
reading and the smallest control and fault readings of each number.
Needs the chips the cell asks for, like ``run.py``; ``--cpu`` runs it at
the configuration's rehearsal sizes instead.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def first_segment(job):
    """The timed path's first segment: relative errors and factors."""
    import numpy as np
    job.segment()
    return (np.concatenate(job.rs.rel_history), np.asarray(job.rs.W),
            np.asarray(job.rs.Ht))


def gaps(got, reference) -> dict:
    """The numbers ``run.py`` compares, and H's own relative gap beside
    them (a look at which factor a gap sits in)."""
    import numpy as np
    from harness import cell as cellmod
    out = cellmod.compare(*got, reference)
    a, b = (np.asarray(x, np.float64) for x in (got[2], reference[1]))
    out["h_gap"] = float(np.linalg.norm(a - b) / np.linalg.norm(b))
    return out


def readings(cell, seed, devices, control: bool, faults: list,
             witness: bool = False) -> dict:
    import jax
    from harness import cell as cellmod
    from harness import faults as faultmod

    t0 = time.perf_counter()
    job = cellmod.Job(cell, seed, devices)
    program = first_segment(job)
    broken = {}
    for name in faults:
        job.rs = job.solver.prepare_state(job.rs.Arep, W0=job.W0,
                                          H0=job.H0)
        with faultmod.FAULTS[name](job):
            try:
                broken[name] = first_segment(job)
            except Exception as e:  # noqa: BLE001 - a fault that crashes
                broken[name] = repr(e)
    W0, Ht0 = job.W0, job.H0.T
    job.rs = job.solver = None
    jax.clear_caches()
    ref, problem = cellmod.reference_problem(job)
    algo, seg = cell.traffic["algo"], cell.traffic["segment_iters"]
    reference = ref.fit(problem, algo, W0, Ht0, seg)
    out = {"seed": seed,
           "program": gaps(program, reference)}
    if control:
        _, low = cellmod.reference_problem(job, "high")
        W, Ht, rels = ref.fit(low, algo, W0, Ht0, seg, precision="high")
        out["control"] = gaps((rels, W, Ht), reference)
        del low
    if control and witness:
        sizes = ref.ROW_BLOCK, ref.NNZ_CHUNK
        ref.ROW_BLOCK, ref.NNZ_CHUNK = sizes[0] // 2, sizes[1] // 2
        try:
            _, other = cellmod.reference_problem(job)
            W, Ht, rels = ref.fit(other, algo, W0, Ht0, seg)
        finally:
            ref.ROW_BLOCK, ref.NNZ_CHUNK = sizes
        out["witness"] = gaps((rels, W, Ht), reference)
        del other
    for name, got in broken.items():
        out[name] = (gaps(got, reference)
                     if not isinstance(got, str) else {"crashed": got})
    out["seconds"] = time.perf_counter() - t0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=0)
    ap.add_argument("--witness", action="store_true")
    ap.add_argument("--segment-iters", type=int, default=None,
                    help="override the traffic's segment length (a look "
                         "at how the readings grow with it)")
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import faults as faultmod, spec
    cell = spec.load_cell(args.workload)
    if args.segment_iters:
        cell.traffic = {**cell.traffic, "segment_iters": args.segment_iters}
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count"
                                   f"={cell.chips}")
        cell.config = {**cell.config, **cell.config["rehearsal"]}
    else:
        from run import setup_compile_cache
        setup_compile_cache()
    import jax
    devices = jax.devices()
    if not args.cpu and (devices[0].platform != "tpu"
                         or len(devices) < cell.chips):
        print("calibrate: needs the cell's TPU chips (or --cpu)",
              file=sys.stderr)
        return 1
    seeds = [int(s) for s in args.seeds.split(",")]
    names = faultmod.faults_for(cell)
    rows = []
    for i, seed in enumerate(seeds):
        row = readings(cell, seed, devices[:cell.chips], i < args.control,
                       names if i < args.faults else [], args.witness)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {}
    for key in ("program", "control", "witness", *names):
        vals = [r[key] for r in rows if key in r and "crashed" not in r[key]]
        if not vals:
            continue
        pick = max if key == "program" else min
        summary[key] = {n: pick(v[n] for v in vals) for n in vals[0]}
    print(json.dumps({"workload": cell.name, "seeds": len(rows),
                      "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
