"""The traffic generator: deterministic per seed, same work for every seed."""
import numpy as np

import traffic

MIX = {"arrival": "poisson", "rate_rps": 2.0, "rows": 4, "n_requests": 50,
       "schedule_seed": 11,
       "prompt_buckets": [2048, 4096, 8192], "prompt_weights": [0.5, 0.3, 0.2],
       "output_min": 16, "output_max": 64}


def _sig(plan):
    return [(len(p.prompt), p.max_new_tokens, round(p.due_s, 12),
             p.prompt[:4].tolist()) for p in plan]


def test_same_seed_same_requests():
    assert _sig(traffic.plan(MIX, 7, 1000)) == _sig(traffic.plan(MIX, 7, 1000))


def test_large_seed_is_accepted_and_differs():
    big = 2 ** 31 + 12345
    a, b = traffic.plan(MIX, big, 1000), traffic.plan(MIX, big + 2 ** 32, 1000)
    assert _sig(a) != _sig(b)


def test_every_seed_gets_the_same_schedule():
    a, b = traffic.plan(MIX, 1, 1000), traffic.plan(MIX, 2, 1000)
    assert _sig(a) != _sig(b)  # the prompt tokens differ
    for f in (lambda p: len(p.prompt), lambda p: p.max_new_tokens,
              lambda p: p.due_s):
        assert list(map(f, a)) == list(map(f, b))
    c = traffic.plan(dict(MIX, schedule_seed=12), 1, 1000)
    assert [len(p.prompt) for p in c] != [len(p.prompt) for p in a]
    assert sorted(len(p.prompt) for p in c) == sorted(len(p.prompt)
                                                      for p in a)


def test_bucket_shares_and_rate():
    pl = traffic.plan(MIX, 3, 1000)
    lens = [len(p.prompt) for p in pl]
    assert [lens.count(t) for t in (2048, 4096, 8192)] == [25, 15, 10]
    assert all(16 <= p.max_new_tokens <= 64 for p in pl)
    assert abs(pl[-1].due_s / len(pl) - 0.5) < 0.1  # mean gap = 1 / rate
    assert all(0 <= p.prompt.min() and p.prompt.max() < 1000 for p in pl)


def test_saturated_fills_rows_with_part_of_their_output():
    mix = dict(MIX, arrival="saturated", output_min=512, output_max=1024)
    pl = traffic.plan(mix, 5, 1000)
    fill = [p for p in pl if p.fill]
    assert [p.index for p in fill] == [0, 1, 2, 3]
    assert all(1 <= p.max_new_tokens <= 1024 for p in fill)
    assert all(512 <= p.max_new_tokens <= 1024 for p in pl if not p.fill)
    assert all(p.due_s == 0.0 for p in pl)
