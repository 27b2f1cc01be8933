"""Record the short device trace that test_devtrace.py reads.

  python tests/bench_chip/record_trace.py --workload <cell> --seed <n> \\
      --seconds <s> --out DIR

Runs the cell once with ``--trace 1`` on the chip and writes under DIR
the raw profile (``profile/``) and ``reduced.json.gz``: the first
ADVANCES traced advances of the first chip, reduced by ``devtrace``, with
the decode steps they ran.  ``data/v5e_trace.json.gz`` is such a file.
"""
import argparse
import gzip
import json
import os
import shutil
import sys

from bench_chip_paths import load

ADVANCES = 40


def keep(path: str, ctx) -> None:
    devtrace = load("devtrace")
    shutil.copytree(ctx.tracer.dir, os.path.join(path, "profile"),
                    dirs_exist_ok=True)
    tr, dev = ctx.trace, ctx.devices[0]
    spans = [h for h in tr.host if h[0] == "advance"][:ADVANCES]
    lo, hi = spans[0][1], spans[-1][2]
    first = ctx.tracer.first
    traced = set(range(first, first + len(spans)))
    w = ctx.work
    steps = [[[r, t], c] for (r, t), c in w["steps"].items()
             if r == 0 and w["step_adv"][(r, t)] in traced]
    small = devtrace.Trace(
        ops={dev: devtrace.overlapping(tr.ops.get(dev, []), lo, hi)},
        modules={dev: devtrace.overlapping(tr.modules.get(dev, []), lo, hi)},
        host=devtrace.overlapping(tr.host, lo, hi), window=(lo, hi))
    with gzip.open(os.path.join(path, "reduced.json.gz"), "wt") as f:
        json.dump({"trace": small.to_json(), "steps": steps}, f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    run = load("run")
    context = run.trace_context

    def grab(*args, **kw):
        ctx = context(*args, **kw)
        keep(a.out, ctx)
        return ctx
    run.trace_context = grab
    os.makedirs(a.out, exist_ok=True)
    run.run(run.parse(["--workload", a.workload, "--seed", str(a.seed),
                       "--seconds", str(a.seconds), "--trace", "1"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
