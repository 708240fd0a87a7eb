"""Readings that the limits of a cell are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 \
        --seconds 3 --first-seed <n>

In one process: the cell's run on ``--seeds`` seeds (the lower readings),
then on ``--control-seeds`` further seeds with the reference in the next
lower precision put in the program's place (the upper readings).  Each run
has a short window at the cell's own load and compares as many answers as a
run does.  Prints one line per run and a JSON summary last.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--first-seed", type=int, default=1_000_003)
    args = ap.parse_args(argv)

    from bench import harness
    readings = {"program": {}, "control": {}}
    plan = [("program", None)] * args.seeds + \
        [("control", "control")] * args.control_seeds
    for i, (side, sub) in enumerate(plan):
        seed = args.first_seed + i
        try:
            r = harness.run_cell(args.workload, seed, args.seconds, False,
                                 substitute=sub)
        except harness.NoAccelerator as e:
            print(f"bench: {e}", file=sys.stderr)
            return 3
        values = {k: v["value"] for k, v in r["checks"].items()}
        for k, v in values.items():
            readings[side].setdefault(k, []).append(v)
        print(f"{side} seed {seed}: {json.dumps(values)} "
              f"correct={r['correct']}", flush=True)
    summary = {side: {k: {"max": max(v), "min": min(v), "n": len(v)}
                      for k, v in d.items()} for side, d in readings.items()}
    print(json.dumps({"workload": args.workload, "readings": readings,
                      "summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
