"""The control of the ``logit_gap`` comparison, at a size a test run
holds: the reference with its weights through float8_e4m3fn, put in the
program's place on the same sampled requests, has to read past the
limit that sound runs of the program stay under.  On the chip the same
readings come from ``benchmarks/chip/control.py`` at the cell's size
(PERF.md, section 2)."""
from bench_chip_paths import load

run = load("run")
check = load("check")


def test_control_fails_where_the_program_passes():
    args = run.parse(["--workload", "qwen3-0.6b.paper-mix", "--seed",
                      str(2**31 + 77), "--seconds", "6"])
    res = run.run(args, rehearse=True, control=True)
    limit = res["checks"]["logit_gap"]["limit"]
    assert limit == check.LOGIT_GAP_LIMIT
    assert res["checks"]["logit_gap"]["value"] <= limit
    assert res["control"]["logit_gap"] > limit
    assert len(res["control"]["per_request"]) >= 3
