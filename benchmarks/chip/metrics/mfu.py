"""Model FLOP/s utilisation of serving, in percent (model-step layer).

The operations the algorithm needs for every request completed in the
window (``counts.request_flops``: causal attention, logits only where a
token is sampled, no padding or recomputation), over the window's wall
time and the chip's bf16 peak times the chips used."""

import counts


def read(view):
    reqs = view.served.requests
    if not reqs or view.peak is None:
        return None
    flops = sum(counts.request_flops(view.cell.conf, len(r.prompt),
                                     len(r.out)) for r in reqs)
    return 100.0 * flops / (view.served.window_s * view.cell.chips
                            * view.peak["bf16_flops_per_s"])
