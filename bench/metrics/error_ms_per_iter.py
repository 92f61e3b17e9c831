"""Device time per iteration of the relative error: the ops the program
runs under its ``error`` scope (the byproduct trace trick and the loop's
square root), from the traced window (``harness/scopes.py``)."""

from harness import scopes


def read(t: dict):
    return scopes.ms_per_iter(t, ("error",))
