"""Model FLOP/s utilisation of RWKV-6 serving, in percent (model-step
layer): the whole step's share of the chip's peak.

The operations the algorithm needs for every request completed in the
window (``rwkv_counts.request_flops``: projections, low-rank mixes and
decay, channel mix, the recurrence in its recurrent form, logits only
where a token is sampled), over the window's wall time and the chip's
bf16 peak times the chips used."""

import rwkv_counts


def read(view):
    reqs = view.served.requests
    if not reqs or view.peak is None:
        return None
    flops = sum(rwkv_counts.request_flops(view.cell.conf, len(r.prompt),
                                          len(r.out)) for r in reqs)
    return 100.0 * flops / (view.served.window_s * view.cell.chips
                            * view.peak["bf16_flops_per_s"])
