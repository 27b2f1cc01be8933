"""Readings for the limit on ``logit_gap``, on the chip, in one process.

  python benchmarks/chip/control.py --workload <cell> --seconds <s> \\
      --seeds 11,12,13

For each seed, one run of the cell as the benchmark makes it (same
build, traffic, window and sample), which reads two numbers on the same
sampled requests:

  program  the widest gap of a served token below the float32
           reference's best (what the run compares with its limit)
  control  the widest gap of the token that the reference puts first
           when its weights go through float8_e4m3fn, the precision one
           step below the configuration's bfloat16

One JSON line per seed; the limit lies between the largest program
reading and the smallest control reading (PERF.md, section 2).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run                                         # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    a = ap.parse_args(argv)
    for seed in [int(s) for s in a.seeds.split(",")]:
        args = run.parse(["--workload", a.workload, "--seed", str(seed),
                          "--seconds", str(a.seconds)])
        res = run.run(args, control=True)
        print(json.dumps({
            "seed": seed, "program": res["checks"]["logit_gap"]["value"],
            "control": res["control"]["logit_gap"],
            "program_per_request": res["control"]["program_per_request"],
            "control_per_request": res["control"]["per_request"],
            "correct": res["correct"],
            "tokens_per_s": res["info"]["tokens_per_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
