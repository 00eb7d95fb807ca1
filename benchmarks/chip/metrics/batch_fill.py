"""Batch fill: real requests per ``generate`` call over ``max_batch``,
averaged over the calls of the window, in percent (engine layer)."""


def read(view):
    t = view.cell.traffic
    cap = t.get("max_batch", t.get("batch"))
    sizes = view.served.batches
    if not sizes:
        return None
    return 100.0 * sum(sizes) / (len(sizes) * cap)
