"""Device idle share, in percent (device layer): 1 - the union of the
device's op intervals over the traced window, averaged over chips."""


def read(view):
    tr = view.trace
    if tr is None or not tr.ops or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.mean_busy_s() / tr.window_s)
