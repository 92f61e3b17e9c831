"""The SpMM kernels' share of their roofline: the least time the chip
needs for an iteration's two sparse products (``harness/work.py``), times
the iterations traced, over the device time of the custom calls (kernels)
that read A's triplets."""


def read(t: dict):
    p = t["products"]
    if p is None or p["kind"] != "spmm" or t["a_custom_calls_s"] <= 0:
        return None
    return 100.0 * p["seconds"] * t["iters"] / t["a_custom_calls_s"]
