"""Share of the row-solves BPP's pivoting loop computed that were needed,
over the window: the program's ``pivot_row_rounds`` (per round, the rows
still off their KKT point) over ``pivot_rounds`` times the rows of a
chunk.  Every round solves every row of its chunk; both half-steps of the
video block are taller than one chunk (``core/bpp.py`` ``ROW_CHUNK``), so
each chunk holds ``ROW_CHUNK`` rows, padded."""


def read(t: dict):
    counters = t.get("counters") or {}
    rounds = counters.get("pivot_rounds", 0)
    if "pivot_row_rounds" not in counters or rounds <= 0:
        return None
    from repro.core.bpp import ROW_CHUNK
    return 100.0 * counters["pivot_row_rounds"] / (rounds * ROW_CHUNK)
