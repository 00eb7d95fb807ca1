"""Decode's share of its dots' roofline, in percent (model-step layer).

Numerator: the least time of the ``decode.*`` dots of the window's
``generate`` calls (``counts.generate_dots``).  Denominator: the device
time of ops in the program's ``decode`` scope, the decode loop's own
copies included (``scopes.py``).  Nothing to read without a trace, or
from a program that names no scopes."""

import scopes


def read(view):
    return scopes.roofline(view, lambda tag: tag.startswith("decode."),
                           lambda path: scopes.step_of(path) == "decode")
