"""The seeded load generator: same seed, same work; other seeds, the same
multiset of sizes and gaps in another order."""
import numpy as np
import pytest

import loadgen

CHAT = {"arrivals": "poisson",
        "prompt": {"median": 128, "sigma": 0.6, "min": 32, "max": 384},
        "output": {"median": 48, "sigma": 0.6, "min": 16, "max": 128}}
BIG_SEED = 2**31 + 12345


def _shape(plan):
    return [(p.arrival_s, len(p.prompt), p.max_new_tokens) for p in plan]


def test_same_seed_same_schedule_and_tokens():
    a = loadgen.plan(CHAT, rate=2.0, seconds=30, seed=BIG_SEED, vocab=1000)
    b = loadgen.plan(CHAT, rate=2.0, seconds=30, seed=BIG_SEED, vocab=1000)
    assert _shape(a) == _shape(b)
    assert [p.prompt for p in a] == [p.prompt for p in b]


def test_other_seed_same_multiset_other_order():
    a = loadgen.plan(CHAT, rate=2.0, seconds=30, seed=7, vocab=1000)
    b = loadgen.plan(CHAT, rate=2.0, seconds=30, seed=8, vocab=1000)
    assert sorted(len(p.prompt) for p in a) == \
        sorted(len(p.prompt) for p in b)
    assert sorted(p.max_new_tokens for p in a) == \
        sorted(p.max_new_tokens for p in b)
    gaps_a = np.diff([0.0] + [p.arrival_s for p in a])
    gaps_b = np.diff([0.0] + [p.arrival_s for p in b])
    assert np.allclose(np.sort(gaps_a), np.sort(gaps_b))
    assert _shape(a) != _shape(b)


def test_lengths_clipped_lognormal_and_rate():
    plan = loadgen.plan(CHAT, rate=4.0, seconds=50, seed=3, vocab=1000)
    assert len(plan) == 200
    p = np.array([len(r.prompt) for r in plan])
    o = np.array([r.max_new_tokens for r in plan])
    assert p.min() >= 32 and p.max() <= 384 and o.min() >= 16 and \
        o.max() <= 128
    assert abs(np.median(p) - 128) <= 3 and abs(np.median(o) - 48) <= 2
    assert (p == 384).any() and (o == 16).any()      # the clips bite
    arrivals = [r.arrival_s for r in plan]
    assert arrivals == sorted(arrivals)
    assert 45 < arrivals[-1] < 55                    # rate x window
    assert all(0 <= t < 1000 for r in plan for t in r.prompt)


def test_offline_queue_arrives_at_once():
    mix = dict(CHAT, arrivals="offline", requests=10)
    plan = loadgen.plan(mix, rate=0.0, seconds=30, seed=1, vocab=50)
    assert len(plan) == 10 and all(p.arrival_s == 0.0 for p in plan)


def test_lateness_p99():
    assert loadgen.p99_ms([]) == 0.0
    assert abs(loadgen.p99_ms([0.001] * 99 + [0.5]) - 5.99) < 0.1


def test_new_arrival_process_is_found_by_name(tmp_path, monkeypatch):
    """An arrival process is one new file beside the others; a mix names
    it.  A name with no file is an error, not a fallback."""
    (tmp_path / "pairs.py").write_text(
        '"""Requests two at a time, one second apart."""\n'
        "import numpy as np\n\nWITHDRAW_AT_CLOSE = False\n\n\n"
        "def count(mix, rate, seconds):\n    return 2 * int(seconds)\n\n\n"
        "def times(mix, rate, n, rng):\n    return np.arange(n) // 2 * 1.0\n")
    monkeypatch.setattr(loadgen, "ARRIVALS_DIR", str(tmp_path))
    plan = loadgen.plan(dict(CHAT, arrivals="pairs"), rate=0.0, seconds=5,
                        seed=BIG_SEED, vocab=100)
    assert [p.arrival_s for p in plan] == [0, 0, 1, 1, 2, 2, 3, 3, 4, 4]
    assert loadgen.arrivals(dict(CHAT, arrivals="pairs")).WITHDRAW_AT_CLOSE \
        is False
    with pytest.raises(ValueError, match="unknown arrivals"):
        loadgen.plan(dict(CHAT, arrivals="bursty"), rate=1.0, seconds=5,
                     seed=1, vocab=100)
