"""Chip smoke: the live disaggregated serving path on one TPU chip.

Serves granite-moe-3b-a800m at its full published width and depth, with
random bf16 weights from a fixed seed, through the entry point a user
calls — ``Server(Orchestrator(...))`` → router → prefill engine (chunked
prefill, Global KV Store) → KV hand-off (store pages bound by reference) →
paged decode engine (page-fused Pallas kernel) — and checks what comes out:

1. every request completes with the requested number of tokens, at least
   one hand-off binds store pages and at least one wave resumes a chunk;
2. the page-fused decode, verify and paged-prefix kernels and the split-KV
   decode agree with their gather-then-attend oracles (``kernels/ref.py``,
   float32 at full matmul precision) on random bf16 inputs at the model's
   attention widths, within ``KERNEL_TOL``;
3. served through the same path, the first ``CHECK_LAYERS`` layers of the
   same weights (full width) give every request a first token among the
   top ``FIRST_TOKEN_TOP`` of a single-engine greedy rollout, and
   last-position logits from one-shot and from chunked prefill (the
   paged-prefix kernel) that agree with a float32 forward: median relative
   error over prompts within ``LOGIT_TOL``.

Why the numerical checks run on a depth cut: with random weights, top-8 of
40 routing re-routes a token wherever two experts' router logits lie within
bf16 rounding of each other, and a re-route changes that token's output by
O(1).  Through the stack this compounds: bf16 and float32 last-position
logits differ by about 1% after one layer, 10% after four and 30% after
eight, and are uncorrelated after 32.  Any two correct bf16 paths that
round differently (another batch shape, a chunk boundary) diverge the same
way, so at full depth the agreement numbers are printed, not checked.

The full-depth workload runs twice: a cold pass that compiles, and a warm
pass that is timed.  Every fleet member shares device 0.

    python chip_smoke.py

With no TPU it exits non-zero before printing a result.  The last line of
stdout is ``{"ok": true, "device": {...}}``; any failed check exits 1.
"""
from __future__ import annotations

import functools
import gc
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "granite-moe-3b-a800m"
SEED = 0
MAX_LEN = 1024
MAX_BATCH = 8
CHUNK_TOKENS = 256
MAX_NEW = 32
# prompts share a 96-token prefix except every fourth; the 300+ token ones
# prefill in chunks of CHUNK_TOKENS, so later waves resume from pages
PROMPT_LENS = (64, 120, 200, 300, 420, 520, 640, 700)
SHARED_PREFIX = 96
# relative error ||bf16 - f32|| / ||f32|| of last-position logits: bf16
# keeps 8 significant bits (relative rounding 2^-9 per op), which a
# two-layer stack accumulates to one or two percent
CHECK_LAYERS = 2
LOGIT_TOL = 0.05
FIRST_TOKEN_TOP = 5
# kernel outputs are rounded to bf16 (2^-9 relative)
KERNEL_TOL = 0.01
SPEC_LEN = 4                # verify queries per row

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileTally:
    """Seconds JAX spent tracing, lowering and compiling, and the number
    of programs compiled, from JAX's own monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += seconds
            self.programs += event == _COMPILE_EVENTS[-1]


def make_requests(vocab: int, lens=PROMPT_LENS, prefix=SHARED_PREFIX,
                  max_new=MAX_NEW, seed=SEED):
    from repro.serving.request import Request

    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, prefix, dtype=np.int32)
    reqs = []
    for i, n in enumerate(lens):
        own = rng.integers(0, vocab, n, dtype=np.int32)
        prompt = own if i % 4 == 3 else np.concatenate([shared, own])[:n]
        reqs.append(Request(rid=i, arrival=0.0, prompt=prompt,
                            max_new_tokens=max_new))
    return reqs


def serve(cfg, params, ecfg, reqs, chunk_tokens):
    """One pass through the front door; returns (summary, wall seconds)."""
    from repro.serving.api import Server
    from repro.serving.orchestrator import Orchestrator, OrchestratorConfig

    server = Server(Orchestrator(cfg, params, OrchestratorConfig(
        n_prefill=1, n_decode=1, engine=ecfg, chunk_tokens=chunk_tokens)))
    t0 = time.perf_counter()
    for r in reqs:
        server.submit(r, at=r.arrival)
    server.drain()          # tokens reach the host as they are committed
    return server.summary(), time.perf_counter() - t0


def _fresh(reqs):
    from repro.serving.request import Request

    return [Request(rid=r.rid, arrival=0.0, prompt=r.prompt,
                    max_new_tokens=r.max_new_tokens) for r in reqs]


def single_engine_rollout(cfg, params, ecfg, reqs):
    """Greedy reference: one prefill engine (no store, one-shot prefill)
    feeding one decode engine.  Returns (streams, last-position logits)."""
    from repro.serving.engine import DecodeEngine, PrefillEngine

    pe = PrefillEngine(cfg, params, ecfg, None, name="ref_p")
    de = DecodeEngine(cfg, params, ecfg, name="ref_d")
    refs = _fresh(reqs)
    logits = []
    for ref, (st, lg) in zip(refs, pe.run_batch(refs)):
        while not de.free_slots:
            de.step()
        de.insert(ref, st, int(jnp.argmax(lg)))
        logits.append(np.asarray(lg, np.float32))
    while de.active:
        de.step()
    return [list(r.generated) for r in refs], logits


def chunked_logits(cfg, params, ecfg, reqs, chunk_tokens):
    """Last-position logits of every prompt prefilled in chunks: prompts
    longer than a chunk resume from their pages through the paged-prefix
    kernel."""
    from repro.serving.engine import PrefillEngine

    pe = PrefillEngine(cfg, params, ecfg, None, name="chunked")
    return [np.asarray(lg, np.float32) for _, lg in
            pe.run_batch(_fresh(reqs), chunk_tokens=chunk_tokens)]


def float32_logits(cfg, params, prompts):
    """Last-position logits of a float32 forward at full matmul precision.

    The weights are the served bf16 values widened to f32 (exact).  A
    whole f32 copy would not fit beside the bf16 weights on one chip, so
    the stacked layers widen one layer at a time inside the scan
    (``param_hook``); embedding and out-norm widen up front, which makes
    f32 the compute dtype of the whole stack.  Prompts pad to one length
    (causal attention: the padding is never seen) so one program serves
    them all."""
    from repro.models import transformer as T

    f32 = functools.partial(jax.tree.map, lambda a: a.astype(jnp.float32))
    head = dict(params, embed=params["embed"].astype(jnp.float32),
                out_norm=params["out_norm"].astype(jnp.float32))
    fwd = jax.jit(functools.partial(T.apply, cfg, mode="train",
                                    logits_slice="last", param_hook=f32))
    width = -(-max(len(p) for p in prompts) // 64) * 64
    out = []
    with jax.default_matmul_precision("highest"):
        for p in prompts:
            toks = np.zeros((1, width), np.int32)
            toks[0, :len(p)] = p
            lg, _, _ = fwd(head, jnp.asarray(toks),
                           logits_at=jnp.asarray([len(p) - 1]))
            out.append(np.asarray(lg[0], np.float32))
    return out


def agreement(cfg, params, ecfg, reqs, chunk_tokens, log):
    """How far served requests agree with a single-engine greedy rollout,
    and one-shot and chunked prefill logits with a float32 forward.  Logs
    each; returns (every first token within the rollout's top
    ``FIRST_TOKEN_TOP``, {prefill path: median relative logit error})."""
    ref_streams, one_shot = single_engine_rollout(cfg, params, ecfg, reqs)
    n = len(reqs)
    first = sum(r.generated[0] == s[0] for r, s in zip(reqs, ref_streams))
    in_top = all(r.generated[0] in np.argsort(lg)[-FIRST_TOKEN_TOP:]
                 for r, lg in zip(reqs, one_shot))
    same = sum(list(r.generated) == s for r, s in zip(reqs, ref_streams))
    log(f"  first tokens equal to the single-engine rollout's greedy "
        f"token: {first}/{n}; all within its top {FIRST_TOKEN_TOP}: "
        f"{in_top}")
    log(f"  token-identical full streams vs single-engine rollout: "
        f"{same}/{n} = {same / n:.3f}")
    gc.collect()
    chunked = chunked_logits(cfg, params, ecfg, reqs, chunk_tokens)
    ref32 = float32_logits(cfg, params, [r.prompt for r in reqs])
    medians = {}
    for name, got in (("one-shot", one_shot), ("chunked", chunked)):
        rel = [float(np.linalg.norm(a - b) / np.linalg.norm(b))
               for a, b in zip(got, ref32)]
        err = max(float(np.max(np.abs(a - b))) for a, b in zip(got, ref32))
        log(f"  {name} prefill logits vs float32: relative error median "
            f"{np.median(rel):.4f} max {max(rel):.4f}; max abs logit error "
            f"{err:.4f}")
        medians[name] = float(np.median(rel))
    return in_top, medians


def kernel_errors(cfg, batch, max_len, block, chunk, seed=SEED):
    """Relative error of each main-path kernel against its oracle in
    ``kernels/ref.py`` (float32, full matmul precision) on random bf16
    inputs at ``cfg``'s attention widths: ``batch`` rows of
    ``max_len // block`` pages, each row holding a random number of tokens,
    and a ``chunk``-token resume wave over each row's paged prefix."""
    from repro.kernels import ops, ref

    h, kv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    nb = max_len // block
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    def f32(x):
        return x.astype(jnp.float32)

    lens = rng.integers(chunk + block, max_len - SPEC_LEN, batch)
    slot_pos = np.arange(nb * block).reshape(1, nb, block)
    pages = 1 + rng.permutation(batch * nb).reshape(batch, nb)  # 0: scratch

    def paged(held):
        """(pos_pages, block_tables) of rows holding ``held[b]`` tokens."""
        pos = np.full((1 + batch * nb, block), -1, np.int32)
        pos[pages] = np.where(slot_pos < held[:, None, None], slot_pos, -1)
        tables = np.where(slot_pos[..., 0] < held[:, None], pages, -1)
        return jnp.asarray(pos), jnp.asarray(tables, jnp.int32)

    k_pages, v_pages = (normal(1 + batch * nb, block, kv, d)
                        for _ in range(2))
    pos, tables = paged(lens)
    q, qs = normal(batch, h, d), normal(batch, SPEC_LEN, h, d)
    pq = jnp.asarray(lens - 1, jnp.int32)
    pqs = jnp.asarray(lens[:, None] - SPEC_LEN + np.arange(SPEC_LEN),
                      jnp.int32)
    start = lens - chunk
    prefix_pos, prefix_tables = paged(start)
    qc, kc, vc = (normal(batch, chunk, n, d) for n in (h, kv, kv))
    positions = jnp.asarray(start[:, None] + np.arange(chunk), jnp.int32)
    k_lin, v_lin = (normal(batch, max_len, kv, d) for _ in range(2))
    valid = jnp.asarray(np.arange(max_len)[None] < lens[:, None])
    paged_kv = (k_pages, v_pages, pos, tables)
    prefix = (kc, vc, k_pages, v_pages, prefix_pos, prefix_tables, positions)
    cases = {
        "paged decode": (
            ops.paged_decode_attention(q, *paged_kv, pq),
            lambda: ref.paged_decode_attention_reference(f32(q), *paged_kv,
                                                         pq)),
        "paged verify": (
            ops.paged_verify_attention(qs, *paged_kv, pqs),
            lambda: ref.paged_verify_attention_reference(f32(qs), *paged_kv,
                                                         pqs)),
        "paged prefix (chunk resume)": (
            ops.paged_prefill_attention(qc, *prefix),
            lambda: ref.paged_prefill_attention_reference(f32(qc), *prefix)),
        "split-KV decode": (
            ops.decode_attention(q, k_lin, v_lin, valid),
            lambda: ref.decode_attention_reference(f32(q), k_lin, v_lin,
                                                   valid)),
    }
    errors = {}
    for name, (got, oracle) in cases.items():
        with jax.default_matmul_precision("highest"):
            want = oracle()
        errors[name] = float(jnp.linalg.norm(f32(got) - want)
                             / jnp.linalg.norm(want))
    return errors


def run(cfg, dtype, *, max_len=MAX_LEN, max_batch=MAX_BATCH,
        chunk_tokens=CHUNK_TOKENS, lens=PROMPT_LENS, prefix=SHARED_PREFIX,
        max_new=MAX_NEW, check_layers=CHECK_LAYERS, log=print) -> bool:
    """Serve, check, report.  Returns True when every check passed."""
    from repro.core import layer_migration as LM
    from repro.models import transformer as T
    from repro.serving.engine import EngineConfig

    ok = True

    def check(cond: bool, what: str) -> None:
        nonlocal ok
        ok &= bool(cond)
        log(f"check {'PASS' if cond else 'FAIL'}: {what}")

    def workload():
        return make_requests(cfg.vocab_size, lens, prefix, max_new)

    def completed(summary, reqs):
        check(summary["n_requests"] == len(reqs)
              and all(len(r.generated) == max_new for r in reqs),
              f"{summary['n_requests']}/{len(reqs)} requests completed with "
              f"{max_new} tokens each")

    tally = CompileTally()
    t0 = time.perf_counter()
    params = jax.jit(functools.partial(T.init, cfg, dtype=dtype))(
        jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n_params = sum(a.size for a in jax.tree.leaves(params))
    log(f"model {cfg.name}: {n_params:,} params "
        f"{jnp.dtype(dtype).name}, init {time.perf_counter() - t0:.1f}s")
    ecfg = EngineConfig(max_len=max_len, max_batch=max_batch, block_size=16)

    _, cold_s = serve(cfg, params, ecfg, workload(), chunk_tokens)
    compile_s, n_compiled = tally.seconds, tally.programs
    gc.collect()
    reqs = workload()
    summary, warm_s = serve(cfg, params, ecfg, reqs, chunk_tokens)
    log(f"compile seconds: {compile_s:.1f} ({n_compiled} programs, "
        f"init and cold pass)")
    log(f"serve wall seconds: cold {cold_s:.2f}, warm {warm_s:.2f} "
        f"({len(reqs)} requests x {max_new} new tokens; "
        f"{tally.programs - n_compiled} programs compiled in the warm pass)")
    log(f"store pages bound: {summary['pages_bound']}")
    log(f"chunk resume waves: {summary['chunk_resume_waves']}")
    completed(summary, reqs)
    check(summary["pages_bound"] > 0, "a hand-off bound store pages")
    check(summary["chunk_resume_waves"] > 0, "a chunk-resume wave ran")
    gc.collect()
    log(f"all {cfg.n_layers} layers (printed, not checked):")
    agreement(cfg, params, ecfg, reqs, chunk_tokens, log)
    gc.collect()

    for name, err in kernel_errors(cfg, max_batch, max_len, ecfg.block_size,
                                   chunk_tokens).items():
        check(err <= KERNEL_TOL,
              f"{name} kernel vs oracle: relative error {err:.5f}")

    cut_cfg = LM.span_config(cfg, 0, check_layers)
    cut = jax.jit(functools.partial(LM.span_params, cfg, start=0,
                                    end=check_layers))(params)
    reqs = workload()
    summary, _ = serve(cut_cfg, cut, ecfg, reqs, chunk_tokens)
    log(f"first {check_layers} layers:")
    completed(summary, reqs)
    in_top, medians = agreement(cut_cfg, cut, ecfg, reqs, chunk_tokens, log)
    check(in_top, f"first tokens within the single-engine rollout's top "
          f"{FIRST_TOKEN_TOP}")
    for name, med in medians.items():
        check(med <= LOGIT_TOL, f"{name} prefill logits agree with the "
              f"float32 forward (median relative error {med:.4f})")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak bytes in use: {stats.get('peak_bytes_in_use', 'n/a')}")
    return ok


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    log = functools.partial(print, flush=True)
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache: {enable_compile_cache()}")
    if not run(configs.get(ARCH), jnp.bfloat16, log=log):
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
