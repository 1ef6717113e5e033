"""Seeded determinism of the traffic generators."""
from chipbench.harness import requests

MIX = {"rate": 12.0, "tenants": 64, "zipf": 1.0, "stratum": 32,
       "schedule_seed": 1,
       "prompt": {"kind": "lognormal", "median": 256, "sigma": 1.0,
                  "min": 16, "max": 1024},
       "output": {"kind": "uniform", "min": 16, "max": 64}}
BIG = 2 ** 40 + 7


def _key(draws):
    return [(d.uid, round(d.due, 9), d.prompt_len, d.output_len, d.tenant,
             d.prompt.tobytes()) for d in draws]


def test_same_seed_same_draws():
    a = requests.open_loop(MIX, BIG, 10.0, 49155)
    b = requests.open_loop(MIX, BIG, 10.0, 49155)
    assert _key(a) == _key(b)
    assert len(a) == 120


def test_seeds_change_content_not_schedule():
    a = requests.open_loop(MIX, BIG, 10.0, 49155)
    b = requests.open_loop(MIX, BIG + 1, 10.0, 49155)
    assert _key(a) != _key(b)
    for field in ("due", "prompt_len", "output_len"):
        assert [getattr(d, field) for d in a] == \
            [getattr(d, field) for d in b]


def test_schedule_seed_changes_order_not_sizes():
    a = requests.open_loop(MIX, BIG, 10.0, 49155)
    b = requests.open_loop(dict(MIX, schedule_seed=2), BIG, 10.0, 49155)
    assert [d.prompt_len for d in a] != [d.prompt_len for d in b]
    assert sorted(d.prompt_len for d in a) == \
        sorted(d.prompt_len for d in b)


def test_arrivals_inside_window_and_sizes_clipped():
    a = requests.open_loop(MIX, 3, 10.0, 49155)
    assert 0.0 <= min(d.due for d in a) and max(d.due for d in a) <= 10.0
    assert all(16 <= d.prompt_len <= 1024 for d in a)
    assert all(16 <= d.output_len <= 64 for d in a)
    assert all(len(d.prompt) == d.prompt_len for d in a)


def test_backlog_deterministic_and_endless():
    g1, g2 = requests.backlog(MIX, BIG, 500), requests.backlog(MIX, BIG, 500)
    a = [next(g1) for _ in range(100)]
    b = [next(g2) for _ in range(100)]
    assert _key(a) == _key(b)
    assert [d.uid for d in a] == list(range(100))
