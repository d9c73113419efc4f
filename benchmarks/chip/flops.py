"""Operations and bytes that the served work needs, computed from shapes.

These count useful work, whatever implements it: a prompt token served
from the store is not prefill work; a mixture of experts computes its
top-k experts per token (not every expert, not a capacity buffer);
attention reads each token's actual context (not padded pages); the head
runs once per produced token.  A later change that removes waste then
raises the shares computed from these counts, and no share can pass 100%
because of padding the program computes.

One multiply-add is 2 operations.  Weights and KV are counted at the
configuration's dtype size.  The counts of one layer, one key and the
head are the architecture's (``archs/<model_type>.py``).
"""
from __future__ import annotations

from typing import Any, Dict

from . import archs

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def token_flops(cfg: Dict[str, Any]) -> float:
    """Operations one token needs in every layer outside attention's
    context term, summed over the layers."""
    return archs.of(cfg).token_flops(cfg)


def attention_flops(cfg: Dict[str, Any], queries: float,
                    keys_per_query: float) -> float:
    """Scores and weighted values of ``queries`` queries each reading
    ``keys_per_query`` keys, over every layer."""
    return archs.of(cfg).attention_flops(cfg, queries, keys_per_query)


def head_flops(cfg: Dict[str, Any]) -> float:
    return archs.of(cfg).head_flops(cfg)


def prefill_flops(cfg: Dict[str, Any], prompt_len: int,
                  cached: int) -> float:
    """Useful operations of prefilling one prompt whose first ``cached``
    tokens were served from the store: the remaining tokens through every
    layer, each attending over its causal context, and the head once."""
    new = prompt_len - cached
    # sum over positions t = cached .. prompt_len-1 of (t + 1) keys
    keys = (prompt_len * (prompt_len + 1) - cached * (cached + 1)) / 2
    return (new * token_flops(cfg) + attention_flops(cfg, 1, keys)
            + head_flops(cfg))


def paged_attention_cost(cfg: Dict[str, Any], queries: int,
                         held: int) -> Dict[str, float]:
    """The page-fused attention kernel's work for one row in one call of
    every layer, ``queries`` queries against ``held`` tokens held in
    pages: ``{"flops": ..., "bytes": ...}``."""
    return archs.of(cfg).paged_attention_cost(cfg, queries, held)


def chunked_prefix_work(prompt_len: int, cached: int,
                        chunk: int) -> list:
    """(prefix held, new queries) of each wave of one prompt prefilled in
    chunks of at most ``chunk`` tokens after ``cached`` store tokens: the
    waves whose prefix is held in pages (store hits and chunk resumes)."""
    out, done = [], cached
    while done < prompt_len:
        n = min(chunk, prompt_len - done) if chunk else prompt_len - done
        if done > 0:
            out.append((done, n))
        done += n
    return out
