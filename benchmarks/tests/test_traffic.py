"""The traffic generator: the same seed gives the same schedule, another seed
another one, and every seed the same multiset of sizes and gaps."""
import json
import os

import numpy as np
from conftest import BENCH

import traffic_gen


def mix():
    with open(os.path.join(BENCH, "traffic", "serve-chat.json")) as f:
        return json.load(f)


def test_same_seed_same_schedule():
    a = traffic_gen.serve_schedule(mix(), vocab_size=50000, seed=2**31 + 17, seconds=20)
    b = traffic_gen.serve_schedule(mix(), vocab_size=50000, seed=2**31 + 17, seconds=20)
    assert a == b


def test_other_seed_other_tokens_same_work():
    a = traffic_gen.serve_schedule(mix(), vocab_size=50000, seed=1, seconds=20)
    b = traffic_gen.serve_schedule(mix(), vocab_size=50000, seed=2**32 + 1, seconds=20)
    assert [x.prompt for x in a] != [x.prompt for x in b]
    assert a[0].prompt[:128] != b[0].prompt[:128]
    # the same arrivals with the same lengths, request for request
    assert [len(x.prompt) for x in a] == [len(x.prompt) for x in b]
    assert [x.max_new_tokens for x in a] == [x.max_new_tokens for x in b]
    assert [x.due_s for x in a] == [x.due_s for x in b]
    assert len(a) == len(b) == round(mix()["rate_rps"] * 20)
    other = traffic_gen.serve_schedule(dict(mix(), schedule_seed=26), vocab_size=50000,
                                       seed=1, seconds=20)
    assert [x.due_s for x in other] != [x.due_s for x in a]
    assert sorted(len(x.prompt) for x in other) == sorted(len(x.prompt) for x in a)


def test_shapes_of_the_mix():
    m = mix()
    s = traffic_gen.serve_schedule(m, vocab_size=50000, seed=3, seconds=50)
    n_prefix = m["shared_prefix_tokens"]
    assert all(x.prompt[:n_prefix] == s[0].prompt[:n_prefix] for x in s)
    tails = [len(x.prompt) - n_prefix for x in s]
    assert min(tails) >= m["tail"]["min"] and max(tails) <= m["tail"]["max"]
    assert abs(np.median(tails) - m["tail"]["median"]) <= 3
    assert all(0 <= x.due_s < 50 for x in s)
    assert all(x.due_s <= y.due_s for x, y in zip(s, s[1:]))
    assert all(1 <= t < 50000 for x in s for t in x.prompt)


def test_backlog_is_data():
    m = dict(mix(), arrival="backlog")
    s = traffic_gen.serve_schedule(m, vocab_size=50000, seed=3, seconds=10)
    assert all(x.due_s == 0.0 for x in s)


def test_bursty_is_data():
    m = dict(mix(), arrival="bursty", burst_secs=1.0, burst_period_s=4.0)
    s = traffic_gen.serve_schedule(m, vocab_size=50000, seed=3, seconds=20)
    assert len(s) == round(m["rate_rps"] * 20)
    assert all(x.due_s % 4.0 < 1.0 for x in s)          # silent outside a burst
    assert all(x.due_s <= y.due_s for x, y in zip(s, s[1:]))
    assert len({int(x.due_s // 4.0) for x in s}) == 5    # every burst is used


def test_train_rows_all_differ():
    job = {"seq_len": 64}
    a = traffic_gen.train_batch(job, vocab_size=50000, seed=2**31 + 5, step=0, rows=8)
    b = traffic_gen.train_batch(job, vocab_size=50000, seed=2**31 + 5, step=1, rows=8)
    assert a.shape == (8, 64) and len({tuple(r) for r in a}) == 8
    assert not (a == b).all()
    assert (a == traffic_gen.train_batch(job, vocab_size=50000, seed=2**31 + 5,
                                         step=0, rows=8)).all()


def test_percentile_is_over_all_values():
    assert traffic_gen.percentile(list(range(1, 101)), 95) == 95
    assert traffic_gen.percentile([5.0], 95) == 5.0
