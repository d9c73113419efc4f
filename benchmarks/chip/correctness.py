"""What decides ``correct``: the served tokens against the plain reference.

Once the window has closed and the server is freed, a sample of the
requests that finished with tokens served in the window is drawn from
the seed, the longest of them always in it, until it holds
``SAMPLE_TOKENS`` served tokens.  The reference (``reference.py``, float32
at highest precision) runs once over each prompt followed by its served
tokens, and reads the logits at every position where a token was served.
The number compared is the widest gap by which a served token's
reference logit lies below the reference's best logit there: zero where
the served greedy token is the reference's argmax, small where two
tokens nearly tie and the served bfloat16 arithmetic picked the other
one, large where a token was altered.

The control (``control_gaps``) puts the reference computed in float8 in
the program's place: at each compared position the token the float8
logits rank first, read on the float32 logits.  It runs in the
calibration script, never in a benchmark run.

A request due in the window that never produced its first token, or was
refused, is lost, and a lost request also makes the run not correct.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from . import reference

SAMPLE_TOKENS = 512
MAX_SAMPLE = 24
MIN_TOKENS = 100


def sample(run, seed: int) -> List[Any]:
    """Finished requests with tokens served in the window, drawn from the
    seed: the longest first, then in random order up to SAMPLE_TOKENS."""
    done = [r for r in run.records.values()
            if r.req.outcome is not None
            and r.req.outcome.value == "completed"
            and any(run.in_window(t) for t in r.tokens)]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: (r.prompt_len + len(r.req.generated),
                                       -r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    out, n = [longest], len(longest.req.generated)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= MAX_SAMPLE:
            break
        out.append(rest[i])
        n += len(rest[i].req.generated)
    return out


def _sequence(rec, max_len: int):
    """(tokens padded to max_len, read positions padded to max_len, n)."""
    prompt = np.asarray(rec.req.prompt, np.int32)
    served = np.asarray(rec.req.generated, np.int32)
    seq = np.concatenate([prompt, served[:-1]])
    n, p = len(served), len(prompt)
    toks = np.zeros(max_len, np.int32)
    toks[:len(seq)] = seq
    read = np.full(max_len, p - 1, np.int32)
    read[:n] = np.arange(p - 1, p - 1 + n)
    return toks, read, n


def gaps(cfg: Dict[str, Any], params, recs, max_len: int,
         precision: str = "float32", control: bool = False) -> np.ndarray:
    """Per served token, the reference's best logit minus its logit for
    the served token (``control``: for the token float8 ranks first)."""
    out = []
    for rec in recs:
        toks, read, n = _sequence(rec, max_len)
        ref = np.asarray(reference.logits(cfg, params, toks, read))[:n]
        if control:
            low = np.asarray(reference.logits(cfg, params, toks, read,
                                              precision="fp8"))[:n]
            pick = low.argmax(-1)
        else:
            pick = np.asarray(rec.req.generated[:n])
        out.append(ref.max(-1) - ref[np.arange(n), pick])
    return np.concatenate(out) if out else np.zeros(0)


def lost(run) -> int:
    """Requests due in the window that were refused or never produced a
    first token."""
    n = 0
    for r in run.window_records():
        outcome = r.req.outcome.value if r.req.outcome is not None else None
        if outcome in ("rejected", "aborted"):
            n += 1
        elif not r.tokens:
            n += 1
    return n


def readings(g: np.ndarray) -> Dict[str, float]:
    """The numbers a check can compare, over the per-token gaps: the
    widest gap, the mean gap, and the share of tokens (in %) that are not
    the reference's first choice."""
    if not len(g):
        return {"max_logit_gap": float("inf"),
                "mean_logit_gap": float("inf"),
                "miss_share": float("inf")}
    return {"max_logit_gap": float(g.max()),
            "mean_logit_gap": float(g.mean()),
            "miss_share": float(np.mean(g > 0) * 100.0)}


def check(run, params, seed, log=print, control: bool = False
          ) -> Dict[str, Any]:
    """``correct`` for a run: each number the cell file limits, at or
    under its limit, at least ``MIN_TOKENS`` tokens compared and no
    request lost.  ``control``: the float8 control in the program's
    place, which has to come out not correct."""
    limits = run.cellfile["limits"]
    recs = sample(run, seed)
    max_len = run.mix["serving"]["max_len"]
    g = gaps(run.cfg, params, recs, max_len, control=control)
    read = readings(g)
    n_lost = lost(run)
    compared = {name: {"value": read[name], "limit": float(limit)}
                for name, limit in limits.items()}
    compared["tokens_compared"] = {"value": int(len(g)), "limit": MIN_TOKENS}
    compared["lost_requests"] = {"value": n_lost, "limit": 0}
    ok = (all(read[name] <= limit for name, limit in limits.items())
          and len(g) >= MIN_TOKENS and n_lost == 0)
    what = "float8 control" if control else "served tokens"
    log(f"check: {len(recs)} requests, {len(g)} {what} vs the float32 "
        f"reference; " + ", ".join(f"{k} {v:.6g}" for k, v in read.items())
        + f"; {n_lost} lost")
    return {"correct": bool(ok), "compared": compared, "readings": read,
            "requests": len(recs)}
