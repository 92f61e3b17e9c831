"""Rounds of BPP's pivoting loop per iteration of the window, summed over
the row chunks of both half-steps and the escalation passes that ran:
the program's own ``pivot_rounds`` counter, read before and after the
window."""


def read(t: dict):
    counters = t.get("counters") or {}
    if "pivot_rounds" not in counters or t["iters"] <= 0:
        return None
    return counters["pivot_rounds"] / t["iters"]
