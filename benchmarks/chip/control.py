"""Readings that the correctness limits are set from, on the chip.

    python3 benchmarks/chip/control.py --workload yi6b.decode \
        --seeds 1,2,3 --seconds 2

For each seed, in one process: draw the weights, serve the cell's mix
for ``--seconds`` through the same path as a run (without a warm-up:
nothing here is timed), and compare a sample
of what was served with the float32 reference (the program's greedy gap,
the lower reading) and the tokens that the float8 control puts first at
the same positions with it (the control's gap, the upper reading).
Prints one JSON line per seed.  The benchmark's own runs do not run the
control.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        cell, cfg, _, _ = harness.open_cell(args.workload)
    except harness.Refused as e:
        print(f"control: refused: {e}", file=sys.stderr)
        return 2
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        sess = harness.Session(cell, cfg, seed)
        served = sess.window(args.seconds)
        failed = harness.failed_requests(served, sess.new, sess.vocab)
        sess.free()
        sample = sess.sample(served)
        gaps = sess.gaps(sample)
        control = sess.gaps(sample, control=True)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "requests": len(served.requests), "failed": failed,
            "sampled_tokens": int(gaps.size),
            "greedy_gap": float(gaps.max()),
            "control_gap": float(control.max()),
            "control_tokens_off": int((control > 0).sum()),
            "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
