"""The generator repeats for a seed, and gives every seed the same
schedule of lengths and due times with other token ids."""

import json
import os

import numpy as np

import traffic_gen

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)["requests"]


def take(params, seed, n):
    s = traffic_gen.RequestStream(params, seed, vocab=49152)
    return [s.next() for _ in range(n)]


def test_same_seed_same_requests():
    p = mix("code-batch")
    a, b = take(p, 2 ** 31 + 7, 40), take(p, 2 ** 31 + 7, 40)
    for (ia, na, ga), (ib, nb, gb) in zip(a, b):
        assert np.array_equal(ia, ib) and na == nb and ga == gb


def test_schedule_seed_fixes_the_schedule_not_the_tokens():
    p = mix("code-steady")
    assert "schedule_seed" in p
    a, b = take(p, 1, 40), take(p, 2 ** 31 + 9, 40)
    assert [(len(i), n, g) for i, n, g in a] == \
        [(len(i), n, g) for i, n, g in b]
    assert not np.array_equal(a[0][0][:8], b[0][0][:8])


def test_schedule_seeds_differ_in_order_not_in_work():
    p = mix("code-batch")
    q = dict(p, schedule_seed=p["schedule_seed"] + 1)
    blk = p["block"]
    la = [(len(i), n) for i, n, _ in take(p, 1, 3 * blk)]
    lb = [(len(i), n) for i, n, _ in take(q, 1, 3 * blk)]
    assert la != lb
    for k in range(3):          # block by block the same multiset
        assert sorted(la[k * blk:(k + 1) * blk]) == \
            sorted(lb[k * blk:(k + 1) * blk])


def test_lengths_follow_the_file():
    p = mix("code-batch")
    reqs = take(p, 3, p["block"])
    lens = sorted(len(i) for i, _, _ in reqs)
    outs = sorted(n for _, n, _ in reqs)
    assert lens[0] >= p["prompt_tokens"]["min"]
    assert lens[-1] <= p["prompt_tokens"]["max"]
    assert outs[0] >= p["output_tokens"]["min"]
    assert outs[-1] <= p["output_tokens"]["max"]
    # the median stratum sits at the file's median
    med = np.median(lens)
    assert 0.8 * p["prompt_tokens"]["median"] < med < \
        1.25 * p["prompt_tokens"]["median"]
    assert max(len(i) + n for i, n, _ in reqs) <= 4096


def test_open_arrivals_fixed_set_of_gaps():
    p = mix("code-steady")
    q = dict(p, schedule_seed=p["schedule_seed"] + 1)
    blk, rate = p["block"], p["arrivals"]["rate_rps"]
    ga = [g for _, _, g in take(p, 5, blk)]
    gb = [g for _, _, g in take(q, 5, blk)]
    assert ga != gb and sorted(ga) == sorted(gb)
    assert abs(sum(ga) / blk - 1 / rate) < 1e-9    # mean gap = 1 / rate
    assert np.std(ga) / np.mean(ga) > 0.8          # exponential: cv near 1


def test_token_stream_repeats_and_is_learnable():
    a = traffic_gen.token_stream(2 ** 31 + 11, 512, 4096)
    b = traffic_gen.token_stream(2 ** 31 + 11, 512, 4096)
    c = traffic_gen.token_stream(12, 512, 4096)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert a.min() >= 0 and a.max() < 512
    # order-1 structure: far fewer distinct successors than a uniform stream
    succ = {}
    for x, y in zip(a[:-1], a[1:]):
        succ.setdefault(int(x), set()).add(int(y))
    assert np.mean([len(v) for v in succ.values()]) < 6


def test_percentile_nearest_rank():
    assert traffic_gen.percentile_nearest_rank(range(1, 101), 95) == 95
    assert traffic_gen.percentile_nearest_rank([5.0], 95) == 5.0
    assert traffic_gen.percentile_nearest_rank([1, 2, 3, 4], 50) == 2
