"""The ``generate`` program's share of its dots' roofline, in percent
(model-step layer).

Numerator: the least time of every dot that the window's ``generate``
calls ran, each the larger of its operations over the bf16 peak and its
least bytes over HBM bandwidth (``counts.generate_dots``).  Denominator:
the device's busy time in the traced window (the union of its op
intervals), in which the window's ``generate`` calls are all the device
does.  Work other than dots (norms, softmax, rotary, cache writes) has
no least time here, so the share is a lower bound and cannot pass 100%
while the counts are right.  Nothing to read without a trace."""

import counts


def read(view):
    tr = view.trace
    if tr is None or view.peak is None:
        return None
    busy = sum(tr.busy_s(d) for d in tr.ops)
    if busy <= 0:
        return None
    t = view.cell.traffic
    least = 0.0
    for b in view.served.batches:
        s, _ = counts.least_time(
            counts.generate_dots(view.cell.conf, b, t["prompt_len"],
                                 t["new_tokens"]),
            view.peak["bf16_flops_per_s"], view.peak["hbm_bytes_per_s"])
        least += s
    return 100.0 * least / busy
