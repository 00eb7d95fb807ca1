"""Prefill attention's share of its dots' roofline, in percent (kernels
layer).

Numerator: the least time of the prefill attention dots
(``prefill.qk``, ``prefill.pv``: causal keys only) of the window's
``generate`` calls (``counts.generate_dots``).  Denominator: the device
time of ops in the program's ``prefill/attention`` scope
(``scopes.py``).  Nothing to read without a trace, or from a program
that names no scopes.

Decode attention is left out: its least time is the KV cache read once,
but the layer loop reads each layer's cache in ops of no scope (slices
of the stacked cache), so the ``attention`` scope does not hold the
time of that work."""

import scopes


def read(view):
    return scopes.roofline(view,
                           lambda tag: tag in ("prefill.qk", "prefill.pv"),
                           lambda path: path == "prefill/attention")
