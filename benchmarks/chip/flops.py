"""Operations and bytes that the served work needs, computed from shapes.

These count useful work, whatever implements it: a prompt token served
from the store is not prefill work; a mixture of experts computes its
top-k experts per token (not every expert, not a capacity buffer);
attention reads each token's actual context (not padded pages); the head
runs once per produced token.  A later change that removes waste then
raises the shares computed from these counts, and no share can pass 100%
because of padding the program computes.

One multiply-add is 2 operations.  Weights and KV are counted at the
configuration's dtype size.
"""
from __future__ import annotations

from typing import Any, Dict

DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _dims(cfg: Dict[str, Any]):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"], int(cfg.get("num_local_experts") or 0),
            int(cfg.get("num_experts_per_tok") or 0),
            cfg["num_hidden_layers"], cfg["vocab_size"])


def token_flops(cfg: Dict[str, Any]) -> float:
    """Operations one token needs in every layer outside attention's
    context term: q/k/v/o projections and the feed-forward (router plus
    the top-k experts, or the dense MLP), summed over the layers."""
    d, h, kv, hd, f, e, k, n, _ = _dims(cfg)
    proj = 2 * d * (h + 2 * kv) * hd + 2 * h * hd * d
    ffn = (2 * d * e + k * 6 * d * f) if e else 6 * d * f
    return float(n * (proj + ffn))


def attention_flops(cfg: Dict[str, Any], queries: float,
                    keys_per_query: float) -> float:
    """Scores and weighted values of ``queries`` queries each reading
    ``keys_per_query`` keys, over every layer."""
    _, h, _, hd, _, _, _, n, _ = _dims(cfg)
    return float(n * 4 * h * hd * queries * keys_per_query)


def head_flops(cfg: Dict[str, Any]) -> float:
    d, *_, v = _dims(cfg)
    return float(2 * d * v)


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


def _kv_bytes_per_token(cfg: Dict[str, Any]) -> float:
    _, _, kv, hd, *_ = _dims(cfg)
    # k and v at the cache dtype, plus one int32 position per token
    return 2 * kv * hd * DTYPE_BYTES[cfg["dtype"]] + 4


def paged_attention_cost(cfg: Dict[str, Any], queries: int,
                         held: int) -> Dict[str, float]:
    """The page-fused attention kernel's work for one row in one call of
    every layer: ``queries`` queries against ``held`` tokens held in
    pages.  Bytes: the held tokens' keys, values and positions read once,
    the queries read at the cache dtype and one float32 (o, l, m) result
    per query and head written."""
    _, h, _, hd, _, _, _, n, _ = _dims(cfg)
    q_bytes = queries * h * hd * DTYPE_BYTES[cfg["dtype"]]
    out_bytes = queries * h * (hd + 2) * 4
    return {"flops": attention_flops(cfg, queries, held),
            "bytes": float(n * (held * _kv_bytes_per_token(cfg)
                                + q_bytes + out_bytes))}


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
