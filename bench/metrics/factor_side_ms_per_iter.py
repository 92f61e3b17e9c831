"""Device time per iteration in which ops ran but none read A and none
was a collective: the k-width work of the Grams, the update rule and the
error term."""


def read(t: dict):
    if t["iters"] <= 0 or t["busy_s"] <= 0:
        return None
    return 1e3 * t["factor_side_s"] / t["iters"]
