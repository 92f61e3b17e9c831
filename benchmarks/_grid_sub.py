"""Subprocess helper for bench_grid_sweep / bench_cost_table: needs fake
devices, so it runs in its own process.  Prints CSV rows to stdout."""

import sys

from repro.util import env

# before any jax import
env.configure(host_device_count=int(sys.argv[1]), platform="cpu")

import jax  # noqa: E402

from repro.core import costmodel, faun, naive  # noqa: E402
from repro.roofline.hlo import collective_stats  # noqa: E402
from repro.util.compat import make_mesh  # noqa: E402


def main():
    p = int(sys.argv[1])
    m, n, k = int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
    mode = sys.argv[5]

    if mode == "grid":
        # sweep all divisor grids pr×pc = p (paper Fig. 7)
        for pr in [d for d in range(1, p + 1) if p % d == 0]:
            pc = p // pr
            if m % pr or n % pc or m % p or n % p:
                continue
            grid = faun.make_faun_mesh(pr, pc)
            txt = faun.lower_step(grid, m, n, k, algo="bpp").compile().as_text()
            st = collective_stats(txt)
            model = costmodel.mpifaun_cost(m, n, k, pr, pc)
            print(f"ROW,grid,{pr},{pc},{st.total_wire_bytes:.0f},"
                  f"{model.words * 4:.0f}")
    elif mode == "table3":
        pr, pc = costmodel.optimal_grid(m, n, p)
        grid = faun.make_faun_mesh(pr, pc)
        txt = faun.lower_step(grid, m, n, k, algo="mu").compile().as_text()
        stf = collective_stats(txt)
        mesh = make_mesh((p,), ("p",))
        txtn = naive.lower_step(mesh, m, n, k, algo="mu").compile().as_text()
        stn = collective_stats(txtn)
        mf = costmodel.mpifaun_cost(m, n, k, pr, pc)
        mn = costmodel.naive_cost(m, n, k, p)
        lb = costmodel.bandwidth_lower_bound_words(m, n, k, p)
        print(f"ROW,table3,faun,{stf.total_wire_bytes:.0f},{mf.words * 4:.0f}")
        print(f"ROW,table3,naive,{stn.total_wire_bytes:.0f},{mn.words * 4:.0f}")
        print(f"ROW,table3,lower_bound,{lb * 4:.0f},{lb * 4:.0f}")


if __name__ == "__main__":
    main()
