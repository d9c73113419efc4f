"""The traffic generator: the same seed gives the same requests, every
seed the same sizes at the same offsets with its own token ids, and
lengths stay in their clips."""
import collections
import pathlib
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import traffic  # noqa: E402

CHAT = traffic.load("chat-prefix")
VOCAB = 49155


def _schedule(seed, rate=5.0):
    gen = traffic.Traffic(CHAT, VOCAB, seed)
    sched = gen.schedule(rate, 10.0, 40.0, 60.0)
    return gen, sched


def test_same_seed_same_requests():
    g1, s1 = _schedule(2**31 + 99)
    g2, s2 = _schedule(2**31 + 99)
    assert [(t, s) for t, s, _ in s1] == [(t, s) for t, s, _ in s2]
    for (_, a, _), (_, b, _) in list(zip(s1, s2))[:20]:
        assert np.array_equal(g1.prompt(a), g2.prompt(b))


def test_other_seed_same_sizes_other_order():
    """Another seed serves the same sizes, in the same order and at the
    same offsets (so it does the same work); only the token ids differ."""
    g1, s1 = _schedule(1)
    g2, s2 = _schedule(2)
    for seg in ("ramp", "window", "tail"):
        a = [(t, s.group, s.suffix, s.output) for t, s, g in s1 if g == seg]
        b = [(t, s.group, s.suffix, s.output) for t, s, g in s2 if g == seg]
        assert a and a == b
    shared = next(s for _, s, _ in s1 if s.group >= 0)
    assert not np.array_equal(g1.prompt(shared), g2.prompt(shared))
    assert not np.array_equal(g1.groups[shared.group],
                              g2.groups[shared.group])


def test_window_holds_rate_times_length_requests():
    for seed in (3, 4, 5):
        _, sched = _schedule(seed, rate=5.0)
        due = [t for t, _, g in sched if g == "window"]
        assert len(due) == 200
        assert 10.0 <= min(due) and max(due) < 50.0
        assert all(a <= b for a, b in zip(due, due[1:]))


@pytest.mark.parametrize("seed", [7, 2**31 + 7])
def test_length_clips(seed):
    mix = CHAT
    gen = traffic.Traffic(mix, VOCAB, seed)
    specs = gen.specs(2000)
    p, o = mix["prompt"], mix["output"]
    for s in specs:
        assert p["min"] <= s.suffix <= p["max"]
        prompt = len(gen.prompt(s))
        assert 1 <= s.output <= o["max"]
        assert prompt + s.output <= mix["max_total"]
    med = np.median([s.suffix for s in specs])
    assert 0.8 * p["median"] < med < 1.2 * p["median"]


def test_prefix_groups_and_share():
    gen = traffic.Traffic(CHAT, VOCAB, 11)
    specs = gen.specs(4000)
    lo, hi = CHAT["prefix_len"]
    assert all(lo <= n <= hi for n in gen.group_len)
    share = np.mean([s.group >= 0 for s in specs])
    assert abs(share - CHAT["prefix_share"]) < 0.03
    counts = collections.Counter(s.group for s in specs if s.group >= 0)
    # Zipf: the most popular group is drawn most often
    assert counts.most_common(1)[0][0] == 0
    s = next(s for s in specs if s.group >= 0)
    prompt = gen.prompt(s)
    assert np.array_equal(prompt[:len(gen.groups[s.group])],
                          gen.groups[s.group])
    assert len(prompt) == len(gen.groups[s.group]) + s.suffix
