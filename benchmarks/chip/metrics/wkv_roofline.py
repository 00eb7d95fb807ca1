"""The WKV recurrence's share of its roofline, in percent (kernels
layer).

Numerator: the least time of the recurrence (``prefill.wkv``,
``decode.wkv``: its operations, and its float32 state read and written
once per layer call, ``rwkv_counts``) over the window's ``generate``
calls.  Denominator: the device time of ops in the program's
``attention`` scope, prefill and decode, which holds the recurrence and
its reads of the layer's state (``scopes.py``).  Nothing to read
without a trace, or from a program that names no scopes."""

import rwkv_counts
import scopes


def read(view):
    sec = scopes.named(view)
    if sec is None or view.peak is None:
        return None
    busy = sum(s for p, s in sec.items()
               if scopes.layer_of(p) == "attention")
    if busy <= 0:
        return None
    return 100.0 * rwkv_counts.least_s(
        view, lambda tag: tag.endswith(".wkv")) / busy
