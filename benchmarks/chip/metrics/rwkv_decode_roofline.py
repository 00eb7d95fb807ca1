"""RWKV-6 decode's share of its roofline, in percent (model-step layer).

Numerator: the least time of decode's dots, recurrence and state bytes
(the ``decode.*`` entries of ``rwkv_counts``) over the window's
``generate`` calls.  Denominator: the device time of ops in the
program's ``decode`` scope, the decode loop's own copies included
(``scopes.py``).  Nothing to read without a trace, or from a program
that names no scopes."""

import rwkv_counts
import scopes


def read(view):
    sec = scopes.named(view)
    if sec is None or view.peak is None:
        return None
    busy = sum(s for p, s in sec.items() if scopes.step_of(p) == "decode")
    if busy <= 0:
        return None
    return 100.0 * rwkv_counts.least_s(
        view, lambda tag: tag.startswith("decode.")) / busy
