"""Device time per iteration of the local update computation: the ops the
program runs under its ``luc_w`` and ``luc_h`` scopes (the update rule's
solve; BPP's pivoting loop), from the traced window
(``harness/scopes.py``)."""

from harness import scopes


def read(t: dict):
    return scopes.ms_per_iter(t, ("luc_",))
