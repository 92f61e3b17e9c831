"""Rows per iteration of the window that BPP's pivoting left off a KKT
point even at its last Tikhonov weight: the program's own
``clamped_rows`` counter, read before and after the window.  On the video
block each such row has come with one more pass of the pivoting loop
over its whole 8,192-row chunk, to its cap."""


def read(t: dict):
    counters = t.get("counters") or {}
    if "clamped_rows" not in counters or t["iters"] <= 0:
        return None
    return counters["clamped_rows"] / t["iters"]
