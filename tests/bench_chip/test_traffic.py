"""The benchmark's traffic generator: seeds reorder one fixed multiset of
requests, lengths follow the paper's mix, every request fits the cache."""
import collections

import numpy as np
import pytest

from bench_chip_paths import CHIP, load

traffic = load("traffic")
MIX = traffic.load(f"{CHIP}/traffic/paper-mix.json")
BIG = 2**31 + 12345


def _stream(seed, replicas=1):
    return traffic.stream(MIX, seed=seed, replicas=replicas,
                          cache_len=2048, vocab=151936)


def test_same_seed_same_stream():
    a, b = _stream(BIG), _stream(BIG)
    assert [(d.arrival, d.prompt, d.decode) for d in a] == \
        [(d.arrival, d.prompt, d.decode) for d in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))


def test_seeds_reorder_the_same_blocks():
    a, b = _stream(BIG), _stream(7)
    assert [(d.prompt, d.decode) for d in a] != \
        [(d.prompt, d.decode) for d in b]
    block = MIX["shuffle_block"]
    for lo in range(0, len(a), block):
        assert collections.Counter((d.prompt, d.decode)
                                   for d in a[lo:lo + block]) == \
            collections.Counter((d.prompt, d.decode)
                                for d in b[lo:lo + block])
        # the block's last arrival is the same sum of the same gaps
        assert a[min(lo + block, len(a)) - 1].arrival == pytest.approx(
            b[min(lo + block, len(b)) - 1].arrival)


def test_requests_fit_the_cache_and_the_grid():
    s = _stream(3, replicas=4)
    grid = set(MIX["prompt_grid"])
    assert all(d.prompt in grid for d in s)
    assert all(1 <= d.decode and d.prompt + d.decode <= 2048 for d in s)
    assert all(len(d.tokens) == d.prompt for d in s)
    assert all(0 <= d.tokens.min() and d.tokens.max() < 151936 for d in s)


def test_rate_and_horizon():
    for replicas in (1, 4):
        s = _stream(5, replicas)
        arr = np.array([d.arrival for d in s])
        assert np.all(np.diff(arr) >= 0)
        assert arr[-1] >= MIX["horizon_s"]
        rate = len(s) / arr[-1]
        assert rate == pytest.approx(MIX["rate_per_replica"] * replicas,
                                     rel=0.1)


def test_lengths_follow_the_paper_mix():
    # Table 1 of arXiv:2408.13510 as the program's workload.py tunes
    # it: mean prompt about 86 tokens and mean decode about 180 over
    # the task weights
    rng = np.random.default_rng(0)
    tasks, prompts, decodes = traffic.draw_lengths(MIX, 20000, rng)
    share = collections.Counter(tasks)
    w = {t["name"]: t["weight"] for t in MIX["tasks"]}
    for name, weight in w.items():
        assert share[name] / 20000 == pytest.approx(
            weight / sum(w.values()), abs=0.015)
    assert 75 < prompts.mean() < 100
    assert 150 < decodes.mean() < 210
    assert prompts.min() >= 4 and prompts.max() <= 1000


def test_snap_is_nearest_in_log_space():
    grid = [8, 16, 32]
    got = traffic.snap(np.array([1, 8, 11, 12, 22, 23, 500]), grid)
    assert got.tolist() == [8, 8, 8, 16, 16, 32, 32]


def test_bursty_arrivals_keep_the_long_run_rate():
    t = traffic.arrival_times(20000, 10.0, "bursty",
                              np.random.default_rng(1))
    assert 20000 / t[-1] == pytest.approx(10.0, rel=0.1)
    with pytest.raises(ValueError):
        traffic.arrival_times(3, 1.0, "diurnal", np.random.default_rng(1))


def test_saturated_mix_is_the_same_requests_faster():
    # the saturated cells' mix draws the same lengths from the same
    # seed; only the rate, the shuffle blocks, the warm-up and the
    # horizon differ
    sat = traffic.load(f"{CHIP}/traffic/paper-mix-saturated.json")
    differ = {k for k in MIX if MIX[k] != sat[k]}
    assert differ == {"name", "rate_per_replica", "shuffle_block",
                      "warmup_s", "horizon_s"}
    assert sat["rate_per_replica"] > 3 * MIX["rate_per_replica"]
    a = traffic.draw_lengths(MIX, 500, np.random.default_rng(9))
    b = traffic.draw_lengths(sat, 500, np.random.default_rng(9))
    assert all(np.array_equal(x, y) for x, y in zip(a[1:], b[1:]))
