#!/usr/bin/env python3
"""Rehearse one cell on the CPU at the configuration's toy sizes
(``rehearsal`` in its file), with Pallas kernels interpreted: the whole
run, the reference comparison included, and no device metric.

    python3 bench/rehearse.py --workload <cell> [--seed n] [--seconds s]
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import spec
    cell = spec.load_cell(args.workload)
    os.environ["JAX_PLATFORMS"] = "cpu"
    if cell.chips > 1:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   f" --xla_force_host_platform_device_count"
                                   f"={cell.chips}")
    import jax
    from harness import cell as cellmod
    from run import print_result
    cell.config = {**cell.config, **cell.config["rehearsal"]}
    out = cellmod.run(cell, args.seed, args.seconds, False,
                      jax.devices()[:cell.chips], on_chip=False)
    print_result(out)
    return 0 if out["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
