"""Device time per iteration of the collective ops (all-reduce,
all-gather, reduce-scatter and the like, by opcode)."""


def read(t: dict):
    if t["collective_count"] == 0 or t["collectives_s"] <= 0:
        return None
    return 1e3 * t["collectives_s"] / t["iters"]
