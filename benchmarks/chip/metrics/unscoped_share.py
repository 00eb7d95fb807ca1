"""Device time in none of the eight layer scopes, in percent of busy
time (model-step layer): loop copies, weight slices, sampling, ops
the compiler left unnamed and the small programs around ``generate``
(``scopes.py``).  Nothing to read without a
trace, or from a program that names no scopes."""

import scopes


def read(view):
    sec = scopes.named(view)
    if sec is None:
        return None
    busy = sum(sec.values())
    if busy <= 0:
        return None
    return 100.0 * sum(s for p, s in sec.items()
                       if scopes.layer_of(p) is None) / busy
