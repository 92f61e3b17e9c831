#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's parts are found by name (``bench/harness/spec.py``).  With
``--trace 0`` the last line of standard output is the result with the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics read
from a profiler trace of the window.  The numbers that decide ``correct``
are printed beside their limits as the last lines of standard error and
under ``compared``, the result's last key.  Without a TPU, or with fewer
chips than the cell needs, the run prints no result and exits with 1.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def print_result(out: dict) -> None:
    for name, c in out["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from harness import cell as cellmod, spec
    cell = spec.load_cell(args.workload)
    try:
        import repro.core.engine  # noqa: F401  (the system under test)
    except ImportError as e:
        cellmod.log(f"cannot import the program from {ROOT}/src: {e}")
        return 2
    cache = setup_compile_cache()
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        cellmod.log(f"no TPU found (JAX platform {devices[0].platform}); "
                    f"this benchmark measures a TPU only")
        return 1
    if len(devices) < cell.chips:
        cellmod.log(f"{cell.name} needs {cell.chips} chips, found "
                    f"{len(devices)}")
        return 1
    cellmod.log(f"{cell.chips} x {devices[0].device_kind}, compile cache "
                f"{cache}")
    out = cellmod.run(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips])
    print_result(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
