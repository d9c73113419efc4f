"""Pipeline-parallel decode (§Perf pair-1, iteration 4).

FSDP weight-gathered decode has a hard collective floor: every step moves
``weights/model_axis`` bytes per chip (llama3-405b: ~600 ms even at int8).
The structural fix is to let each data-axis slice OWN a contiguous span of
layers outright (pipeline stages × tensor parallelism within a stage):

* per-chip weight residency is identical to 2-D FSDP (W / (stages × TP)),
* but nothing is gathered — the only inter-stage traffic is the (µB, d)
  activation handed between stages via ``collective_permute``.

Decode batch B is split into ``n_stages`` microbatches fed GPipe-style;
after the fill latency every stage works every tick.  Implemented as a
``shard_map`` over the "data" axis with the "model" axis left to GSPMD
(per-stage tensor parallelism stays automatic).

Restrictions: dense decoder-only archs (uniform block pattern), decode step
only.  Layer count is padded to a multiple of the stage count with exact
identity blocks (zero output projections — residual passthrough).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..models import layers as L
from ..models import quant as Q
from ..models import transformer as T
from ..models.config import BlockKind, ModelConfig


def pad_layers(cfg: ModelConfig, n_stages: int) -> Tuple[int, int]:
    """(layers_per_stage, n_pad) so stages divide the (padded) stack."""
    total = -(-cfg.n_layers // n_stages) * n_stages
    return total // n_stages, total - cfg.n_layers


def pad_stacked_params(cfg: ModelConfig, params, n_pad: int):
    """Append ``n_pad`` identity layers (zero wo / w_down => residual
    passthrough) to the stacked group params."""
    if n_pad == 0:
        return params
    def pad_leaf(path, a):
        name = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        padder = jnp.zeros((n_pad,) + a.shape[1:], a.dtype)
        return jnp.concatenate([a, padder], axis=0)
    g0 = jax.tree_util.tree_map_with_path(pad_leaf, params["groups"][0])
    out = dict(params)
    out["groups"] = (g0,)
    return out


def pad_stacked_cache(cache, n_pad: int):
    if n_pad == 0:
        return cache
    def pad_leaf(a):
        return jnp.concatenate(
            [a, jnp.zeros((n_pad,) + a.shape[1:], a.dtype)], axis=0)
    out = dict(cache)
    out["groups"] = (jax.tree.map(pad_leaf, cache["groups"][0]),)
    return out


def build_pipeline_decode(cfg: ModelConfig, mesh, batch: int):
    """Returns decode_fn(params, tokens, cache) -> (logits, new_cache),
    pipelined over the mesh's "data" axis."""
    assert len(cfg.block_pattern) == 1 and \
        cfg.block_pattern[0] in (BlockKind.ATTENTION,
                                 BlockKind.LOCAL_ATTENTION), \
        "pipeline decode: dense uniform stacks only"
    n_stages = mesh.shape["data"]
    assert batch % n_stages == 0, (batch, n_stages)
    mb = batch // n_stages
    per_stage, n_pad = pad_layers(cfg, n_stages)
    window = cfg.sliding_window

    def stage_fn(params_st, tokens, cache_g, lengths):
        """One device = one stage.  params_st: (per_stage, ...) layer stack;
        cache_g: stage's cache slice (per_stage, B, L, KV, D...)."""
        stage = jax.lax.axis_index("data")
        compute_dtype = params_st["out_norm"].dtype
        embed = Q.dequant(params_st["embed"], compute_dtype)

        n_ticks = 2 * n_stages - 1
        logits_acc = jnp.zeros((batch, cfg.vocab_size), jnp.float32)

        def tick(carry, t):
            cache_g, x_recv, logits_acc = carry
            m = t - stage                      # µbatch index at this stage
            valid = (m >= 0) & (m < n_stages)
            mc = jnp.clip(m, 0, n_stages - 1)
            # µbatch rows [mc*mb, (mc+1)*mb)
            toks_m = jax.lax.dynamic_slice_in_dim(tokens, mc * mb, mb, 0)
            len_m = jax.lax.dynamic_slice_in_dim(lengths, mc * mb, mb, 0)
            x0 = embed[toks_m].astype(embed.dtype)
            x = jnp.where(stage == 0, x0, x_recv)
            positions = len_m[:, None]

            cache_m = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, mc * mb, mb, 1),
                cache_g)

            def layer(xc, xs):
                x, st = xc, xs[1]
                lp = xs[0]
                y, ns, _ = T._apply_block(
                    cfg, cfg.block_pattern[0], lp, x,
                    positions=positions, state=st, mode="decode",
                    frames=None, moe_impl="sorted", moe_cf=None)
                return y, ns
            x, new_cache_m = jax.lax.scan(layer, x, (params_st["groups"],
                                                     cache_m))
            # masked write-back of the µbatch cache rows
            def put(a, new):
                cur = jax.lax.dynamic_slice_in_dim(a, mc * mb, mb, 1)
                sel = jnp.where(valid, new, cur)
                return jax.lax.dynamic_update_slice_in_dim(a, sel, mc * mb, 1)
            cache_g = jax.tree.map(put, cache_g, new_cache_m)

            # final stage: normalized logits for this µbatch
            h = L.rms_norm(x, params_st["out_norm"], cfg.rms_eps)
            if cfg.tie_embeddings:
                lg = jnp.einsum("bsd,vd->bsv", h, embed)[:, -1]
            else:
                lg = jnp.einsum("bsd,dv->bsv", h,
                                Q.dequant(params_st["unembed"],
                                          compute_dtype))[:, -1]
            is_last = stage == n_stages - 1
            upd = jnp.where(valid & is_last, lg.astype(jnp.float32), 0.0)
            cur = jax.lax.dynamic_slice_in_dim(logits_acc, mc * mb, mb, 0)
            logits_acc = jax.lax.dynamic_update_slice_in_dim(
                logits_acc, cur + upd, mc * mb, 0)

            # hand activations to the next stage
            x_send = jax.lax.ppermute(
                x, "data", [(i, i + 1) for i in range(n_stages - 1)])
            return (cache_g, x_send, logits_acc), ()

        (cache_g, _, logits_acc), _ = jax.lax.scan(
            tick, (cache_g, jnp.zeros((mb, 1, cfg.d_model),
                                      embed.dtype), logits_acc),
            jnp.arange(n_ticks))
        # only the last stage holds real logits: sum-reduce across stages
        logits = jax.lax.psum(logits_acc, "data")
        return logits, cache_g, lengths + 1

    def decode_fn(params, tokens, cache):
        p_specs = {
            "embed": P(),
            "out_norm": P(),
            "groups": jax.tree.map(lambda _: P("data"), params["groups"][0]),
        }
        if "unembed" in params:
            p_specs["unembed"] = P()
        p_in = {k: params[k] for k in p_specs if k != "groups"}
        p_in["groups"] = params["groups"][0]     # the stacked layer dict
        c_specs = jax.tree.map(lambda _: P("data"), cache["groups"][0])
        logits, new_g, new_len = jax.shard_map(
            stage_fn, mesh=mesh,
            in_specs=(p_specs, P(), c_specs, P()),
            out_specs=(P(), c_specs, P()),
            axis_names={"data"}, check_vma=False)(p_in, tokens, cache["groups"][0],
                                  cache["lengths"])
        new_cache = {"lengths": new_len, "groups": (new_g,),
                     "rem": cache.get("rem", ())}
        return logits, new_cache

    return decode_fn, per_stage, n_pad
