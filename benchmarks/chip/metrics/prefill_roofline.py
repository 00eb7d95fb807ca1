"""Prefill's share of its dots' roofline, in percent (model-step layer).

Numerator: the least time of the ``prefill.*`` dots of the window's
``generate`` calls (``counts.generate_dots``, the larger of operations
over the bf16 peak and least bytes over HBM bandwidth).  Denominator:
the device time of ops in the program's ``prefill`` scope
(``scopes.py``).  Nothing to read without a trace, or from a program
that names no scopes."""

import scopes


def read(view):
    return scopes.roofline(view, lambda tag: tag.startswith("prefill."),
                           lambda path: scopes.step_of(path) == "prefill")
