"""Run one cell of the chip benchmark.

    python3 benchmarks/chip/run.py --workload yi6b.decode --seed 7 \
        --seconds 45 --trace 0

Prints one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device`` and ``checks``
(each compared number with its limit, also the last lines of standard
error).  Exits non-zero, with no such line, without a chip that
``peaks.json`` knows, or outside a checkout of the program.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell, cfg, devices, peak = harness.open_cell(args.workload)
        result = harness.run(cell, cfg, args.seed, args.seconds,
                             bool(args.trace), T_START, devices, peak)
    except harness.Refused as e:
        print(f"chipbench: refused: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
