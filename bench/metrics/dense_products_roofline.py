"""The dense local products' share of their roofline: the least time the
chip needs for an iteration's two products (``harness/work.py``), times
the iterations traced, over the device time of the ops that read A."""


def read(t: dict):
    p = t["products"]
    if p is None or p["kind"] != "dense" or t["a_ops_s"] <= 0:
        return None
    return 100.0 * p["seconds"] * t["iters"] / t["a_ops_s"]
