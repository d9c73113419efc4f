"""The one traffic generator: reads a mix's parameters from
``traffic/<name>.json`` and makes its requests from the run's seed.

Sizes, gaps and their order come from the mix's own ``generator_seed``,
so every run seed serves the same requests at the same offsets: the same
prompt and output lengths, prefix groups and arrivals.  The run's seed
draws only the token ids (and, elsewhere, the weights).  Runs with
different seeds then do the same work; a seed that also ordered the
requests moved the cell's TTFT tail by more than its bound, because with
some fifty requests in a window the tail is the few that meet a burst.

Lengths are log-normal (median, sigma) clipped to [min, max]; prompt plus
output is clipped to ``max_total``.  Shared prefixes: ``prefix_groups``
documents of ``prefix_len`` tokens, picked with Zipf(``prefix_zipf``)
popularity by a ``prefix_share`` of the requests, each followed by the
request's own suffix.  The Poisson and Zipf arithmetic is that of the
program's ``serving/workload.py``, copied so that the yardstick stays
fixed.

Arrivals are Poisson at the cell's rate, open loop.  The schedule has
three segments, ramp (before the window), window and tail; each holds a
fixed number of requests, rate x length, whose exponential gaps are
scaled to the segment's length exactly.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Dict, List

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent


def load(name: str) -> Dict[str, Any]:
    with open(HERE / "traffic" / f"{name}.json") as f:
        mix = json.load(f)
    if mix["name"] != name:
        raise ValueError(f"traffic/{name}.json names {mix['name']!r}")
    return mix


@dataclasses.dataclass
class Spec:
    """One request's shape: its prefix group (-1: none), own suffix length
    and output length."""
    group: int
    suffix: int
    output: int


def _lognormal(rng: np.random.Generator, p: Dict[str, float], n: int):
    x = rng.lognormal(np.log(p["median"]), p["sigma"], n)
    return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)


def zipf_popularity(n: int, s: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    pop = ranks ** (-s)
    return pop / pop.sum()


class Traffic:
    """The requests of one mix for one run seed."""

    def __init__(self, mix: Dict[str, Any], vocab: int, seed: int):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        fixed = np.random.default_rng(mix["generator_seed"])
        self._fixed = fixed
        g = int(mix.get("prefix_groups", 0))
        lo, hi = mix.get("prefix_len", [0, 0])
        self.group_len = fixed.integers(lo, hi + 1, g) if g else []
        self.groups = [self.rng.integers(0, vocab, int(n), dtype=np.int32)
                       for n in self.group_len]

    # -- sizes -------------------------------------------------------------
    def specs(self, n: int) -> List[Spec]:
        """The next ``n`` request shapes, drawn from the mix's fixed
        generator (the same for every run seed)."""
        mix, rng = self.mix, self._fixed
        g = len(self.groups)
        share = float(mix.get("prefix_share", 0.0))
        pop = zipf_popularity(g, mix["prefix_zipf"]) if g else None
        suffix = _lognormal(rng, mix["prompt"], n)
        output = _lognormal(rng, mix["output"], n)
        shared = rng.random(n) < share if g else np.zeros(n, bool)
        group = (rng.choice(g, size=n, p=pop) if g
                 else np.zeros(n, np.int64))
        out = []
        for i in range(n):
            gid = int(group[i]) if shared[i] else -1
            plen = int(suffix[i]) + (int(self.group_len[gid]) if gid >= 0
                                     else 0)
            o = int(min(output[i], mix["max_total"] - plen))
            out.append(Spec(gid, int(suffix[i]), max(o, 1)))
        return out

    def prompt(self, spec: Spec) -> np.ndarray:
        own = self.rng.integers(0, self.vocab, spec.suffix, dtype=np.int32)
        if spec.group < 0:
            return own
        return np.concatenate([self.groups[spec.group], own])

    # -- arrivals ----------------------------------------------------------
    def schedule(self, rate: float, ramp_s: float, window_s: float,
                 tail_s: float) -> List[tuple]:
        """[(due offset s, Spec, segment)] sorted by due time: ``ramp``
        before the window, ``window``, ``tail`` after it."""
        out = []
        t0 = 0.0
        for seg, length in (("ramp", ramp_s), ("window", window_s),
                            ("tail", tail_s)):
            n = int(round(rate * length))
            if n == 0:
                t0 += length
                continue
            specs = self.specs(n)
            gaps = self._fixed.exponential(1.0, n)
            # scale so the n arrivals fill [t0, t0 + length) exactly: the
            # first comes a fraction of a gap in, the last before the end
            cum = np.cumsum(gaps) / (gaps.sum() + self._fixed.exponential())
            out += [(t0 + length * float(c), s, seg)
                    for c, s in zip(cum, specs)]
            t0 += length
        return out


def make_request(rid: int, prompt: np.ndarray, output: int):
    from repro.serving.request import Request
    return Request(rid=rid, arrival=0.0, prompt=prompt,
                   max_new_tokens=int(output))
