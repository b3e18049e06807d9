"""The traffic generator: a seed fixes the schedule, and every seed asks
for the same work in another order."""
import numpy as np
import pytest

from bench import harness, traffic


# an open-loop mix of bursty chat: base rate 1/s, bursts at 4x for 2 s
# between base phases of mean 8 s, a 51 s cycle
CHAT = {"loop": "open", "rate": 1.0,
        "burst": {"factor": 4.0, "seconds": 2.0, "gap_mean_s": 8.0},
        "horizon_s": 51.0,
        "prompt_len": {"kind": "fixed", "value": 64},
        "output_len": {"kind": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 8, "max": 64},
        "budgets": {"bits": [2, 4, 6], "shares": [0.3, 0.4, 0.3]}}


def mixes():
    return {"decode": traffic.load(harness.BENCH_DIR, "decode"),
            "chat": CHAT}


@pytest.mark.parametrize("name", ["decode", "chat"])
def test_same_seed_same_requests(name):
    mix = mixes()[name]
    a = traffic.make_items(mix, 2**31 + 17, 32000, 64)
    b = traffic.make_items(mix, 2**31 + 17, 32000, 64)
    assert [(i.max_new_tokens, i.budget_bits) for i in a] == \
        [(i.max_new_tokens, i.budget_bits) for i in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("name", ["decode", "chat"])
def test_seeds_ask_for_the_same_work(name):
    mix = mixes()[name]
    a = traffic.make_items(mix, 1, 32000, 90)
    b = traffic.make_items(mix, 2, 32000, 90)
    assert [(i.max_new_tokens, i.budget_bits) for i in a] == \
        [(i.max_new_tokens, i.budget_bits) for i in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)


def test_every_stretch_mixes_lengths_and_budgets():
    items = traffic.make_items(mixes()["decode"], 0, 32000, 96)
    for start in range(0, 96, 16):
        block = items[start:start + 16]
        lens = sorted(i.max_new_tokens for i in block)
        assert lens[0] < 100 and lens[-1] > 160
        assert {i.budget_bits for i in block} == {2, 4, 6}


def test_lengths_follow_the_mix():
    mix = mixes()["decode"]
    items = traffic.make_items(mix, 3, 32000, 999)
    out = np.array([i.max_new_tokens for i in items])
    assert out.min() >= 64 and out.max() <= 256
    assert abs(np.median(out) - 128) <= 2
    assert {i.prompt.shape[0] for i in items} == {32}
    shares = np.bincount([i.budget_bits for i in items])[[2, 4, 6]] / 999
    assert np.allclose(shares, 1 / 3, atol=0.01)


def test_open_schedule_is_fixed_by_the_seed():
    mix = mixes()["chat"]
    a = traffic.open_schedule(mix, 5)
    b = traffic.open_schedule(mix, 5)
    c = traffic.open_schedule(mix, 6)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    h, n = mix["horizon_s"], len(a) // 3
    assert np.all(np.diff(a) >= 0) and a[-1] <= 3 * h
    assert np.allclose(a[:n] + h, a[n:2 * n])


def test_open_schedule_offers_the_stated_rate():
    mix = dict(mixes()["chat"], horizon_s=2000.0)
    times = traffic.open_schedule(mix, 7)
    times = times[times < mix["horizon_s"]]
    rate = len(times) / mix["horizon_s"]
    assert abs(rate - traffic.mean_rate(mix)) / traffic.mean_rate(mix) < 0.1
    counts = [len(traffic.open_schedule(mix, s)) for s in (1, 2, 3)]
    assert len(set(counts)) == 1
