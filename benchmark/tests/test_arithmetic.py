"""Percentiles and the traffic generator, from a seed. CPU only, no jax.

Run by hand: ``python -m pytest benchmark/tests -q`` (the tier-1 command
collects ``tests/`` only)."""

import collections

import pytest

from benchmark.lib import costs, harness, stats, traffic


def test_percentile_interpolates_between_order_statistics():
    xs = [4.0, 1.0, 3.0, 2.0]
    assert stats.percentile(xs, 0) == 1.0
    assert stats.percentile(xs, 100) == 4.0
    assert stats.median(xs) == 2.5
    assert stats.percentile(xs, 95) == pytest.approx(3.85)
    assert stats.percentile([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_every_seed_gets_the_same_sizes_in_the_same_order():
    mix = traffic.load_mix("rollout-long")
    a = traffic.steady_plan(mix, 7, 1000)
    b = traffic.steady_plan(mix, 7, 1000)
    c = traffic.steady_plan(mix, 2**31 + 12345, 1000)  # over 32 signed bits
    assert a == b
    assert a != c

    def sizes(plan):
        return collections.Counter((len(p["prompt"]), p["budget"], p["rank"])
                                   for p in plan)
    assert sizes(a) == sizes(c)
    # the order of submission is work (what decodes while the rest
    # prefills, and what an engine short of pages admits), so it is the
    # same for every seed, and shuffled
    ranks = [p["rank"] for p in a]
    assert ranks == [p["rank"] for p in c] and ranks != sorted(ranks)
    lo, hi = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    assert all(lo <= len(p["prompt"]) <= hi for p in a)
    assert all(0 < t < 1000 for p in a for t in p["prompt"])
    # what the client offers is the mix's number, not the engine's
    assert len(a) == mix["offered_requests"] == 18
    assert len(traffic.steady_plan(dict(mix, offered_requests=40), 7,
                                   1000)) == 40
    ranked = sorted(a, key=lambda p: p["rank"])
    assert [len(p["prompt"]) for p in ranked] == sorted(
        len(p["prompt"]) for p in a)


def test_quantiles_cover_the_range_and_pareto_is_heavy_tailed():
    par = traffic.size_set(
        {"dist": "pareto", "lo": 2048, "hi": 8192, "alpha": 1.2}, 64)
    assert par == sorted(par) and 2048 <= par[0] and par[-1] <= 8192
    assert par[32] < (2048 + 8192) / 2 < par[-1]   # median below the middle
    assert traffic.size_set({"dist": "fixed", "lo": 7, "hi": 7}, 3) == [7] * 3
    with pytest.raises(harness.Refused):
        traffic.quantile({"dist": "no-such", "lo": 1, "hi": 2}, 0.5)


def test_edge_rate_runs_from_arrival_to_arrival():
    # 8 tokens every 0.2 s
    arrivals = [(0.2 * i, 8) for i in range(60)]
    rate, tokens, e0, e1 = stats.edge_rate(arrivals, 1.0, 9.0)
    assert rate == pytest.approx(40.0)
    assert (e0, e1) == (pytest.approx(1.0), pytest.approx(9.0))
    assert tokens == 8 * 40
    # the window's phase against the arrivals does not move the rate,
    # which a count over [t0, t1) would move by 2.5%
    for shift in (0.0005, 0.05, 0.1, 0.1999):
        assert stats.edge_rate(arrivals, 1.0 + shift,
                               9.0 + shift)[0] == pytest.approx(40.0)
    # lines of unequal size count what they carry
    uneven = [(0.0, 8), (1.0, 16), (1.5, 8), (2.0, 8)]
    assert stats.edge_rate(uneven, 0.0, 1.9)[:2] == (pytest.approx(16.0), 32)
    with pytest.raises(ValueError):
        stats.edge_rate(arrivals, 1.0, 12.5)


def test_costs_of_the_published_sizes():
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    c7 = harness.load_config(
        os.path.join(here, "configs", "qwen2.5-7b.json"))["config"]
    assert costs.kv_bytes_per_token(c7) == 2048 * c7["num_hidden_layers"]
    # 16 layers of 233 M matmul parameters and the 545 M of the head
    assert costs.matmul_params(c7) == pytest.approx(4.274e9, rel=0.001)
    assert costs.decode_step_bytes(c7, 0) == 2 * costs.matmul_params(c7)
    assert costs.decode_step_bytes(c7, 1000) - costs.decode_step_bytes(
        c7, 0) == 1000 * 32768
