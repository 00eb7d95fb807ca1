"""The MLP's share of its dots' roofline, in percent (kernels layer).

Numerator: the least time of the MLP dots (``*.gate_up``, ``*.down``) of
the window's ``generate`` calls (``counts.generate_dots``).
Denominator: the device time of ops in the program's ``mlp`` scope,
prefill and decode (``scopes.py``).  Nothing to read without a trace,
or from a program that names no scopes."""

import scopes


def read(view):
    return scopes.roofline(view,
                           lambda tag: tag.endswith((".gate_up", ".down")),
                           lambda path: scopes.layer_of(path) == "mlp")
