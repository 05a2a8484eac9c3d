"""The traffic generator: seeded, and the same work on every seed."""

import collections

import numpy as np
import pytest
import smoke

from soibench import traffic


@pytest.mark.parametrize("name", ["chat-closed", "long-closed", "rag-open"])
def test_every_seed_sends_the_same_sizes_in_another_order(name):
    mix = smoke.mix(name)
    a = traffic.Traffic(mix, 1000, 2 ** 31 + 7)
    b = traffic.Traffic(mix, 1000, 2 ** 31 + 7)
    c = traffic.Traffic(mix, 1000, 2 ** 31 + 8)
    assert a.sizes() == b.sizes()
    assert np.array_equal(a.take(0).tokens, b.take(0).tokens)
    assert a.sizes() != c.sizes()
    assert collections.Counter(a.sizes()) == collections.Counter(c.sizes())
    p, o = mix["prompt"], mix["output"]
    pre = mix.get("prefix", {}).get("len", 0)
    for tl, n in a.sizes():
        assert p["lo"] + pre <= tl <= p["hi"] + pre
        assert o["lo"] <= n <= o["hi"]


def test_lengths_are_log_uniform():
    x = traffic.log_uniform(128, 1024, 4096)
    assert x.min() >= 128 and x.max() <= 1024
    # log-uniform: as many lengths below the geometric mean as above it
    assert abs(np.mean(x < np.sqrt(128 * 1024)) - 0.5) < 0.01


def test_open_schedule_fills_the_window_at_the_mix_rate():
    mix = smoke.mix("rag-open", slots=3)
    mix["rate_hz"] = 6.0
    runs = [traffic.Traffic(mix, 1000, s).schedule(40.0) for s in (1, 2)]
    assert len(runs[0]) == len(runs[1])
    assert abs(len(runs[0]) / 40.0 - 6.0) < 0.6
    for sched in runs:
        due = [r.due for r in sched]
        assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 40.0
        bursts = collections.Counter(due)
        assert max(bursts.values()) > 1           # requests come in bursts
    # the tenants' shared prefixes lead every prompt
    t = traffic.Traffic(mix, 1000, 3)
    req = t.take(0)
    assert any(np.array_equal(req.tokens[:req.prefix_len], p)
               for p in t.tenant_prompts())


def test_zipf_tenants_favour_the_first():
    t = collections.Counter(traffic.zipf_tenants(8, 1.1, 1000).tolist())
    assert t[0] > t[1] > t[7] > 0
