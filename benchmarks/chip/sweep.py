"""Sweep the offered rate of an open-loop cell to find its knee, on the chip.

    python3 benchmarks/chip/sweep.py --workload yi6b.arrivals \
        --rates 1.5,2,2.5,3,3.5 --seconds 40 --seed 1

One process sets the cell up once, then serves its mix at each rate in
turn for ``--seconds``.  Prints one JSON line per rate: requests served
per second, the median and 90th percentile of due-to-completion time,
the batch fill, and the mean latency of the last tenth of requests over
that of the first tenth (above about 1.5 the queue grows through the
window: the rate is past the knee).  The knee found is written into the
cell's traffic file by hand, with the sweep in PERF.md.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import traffic  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    try:
        cell, cfg, _, _ = harness.open_cell(args.workload)
        if cell.traffic["loop"] != "open":
            raise harness.Refused(f"{cell.name} is not an open loop")
    except harness.Refused as e:
        print(f"sweep: refused: {e}", file=sys.stderr)
        return 2
    sess = harness.Session(cell, cfg, args.seed)
    for k in sess.batch_sizes():
        t = time.perf_counter()
        sess.serve(traffic.warm_up_prompts(args.seed, k, sess.prompt_len,
                                           sess.vocab))
        first = time.perf_counter() - t
        t = time.perf_counter()
        sess.serve(traffic.warm_up_prompts(args.seed, k, sess.prompt_len,
                                           sess.vocab))
        print(json.dumps({"batch": k, "first_s": first,
                          "warm_s": time.perf_counter() - t}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        cell.traffic["rate_per_s"] = rate
        served = sess.window(args.seconds)
        lat = [r.done - r.due for r in served.requests]
        tenth = max(1, len(lat) // 10)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "served_per_s": len(lat) / served.window_s,
            "window_s": served.window_s,
            "latency_p50_s": traffic.quantile(lat, 0.5),
            "latency_p90_s": traffic.quantile(lat, 0.9),
            "batch_fill": sum(served.batches)
            / (len(served.batches) * sess.max_batch),
            "growth": (sum(lat[-tenth:]) / tenth) / (sum(lat[:tenth]) / tenth),
            "compiles_in_window": served.compiles}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
