"""Device time per iteration of the factor Grams: the ops the program
runs under its ``gram_w`` and ``gram_h`` scopes, from the traced window
(``harness/scopes.py``)."""

from harness import scopes


def read(t: dict):
    return scopes.ms_per_iter(t, ("gram_",))
