"""One run of one cell: set-up, the measured window, the correctness
check, and the metrics.

Set-up (``setup_s``, from process start to the window's opening):
weights from the seed, a warm-up server that sweeps every prefill shape
the mix can use — each (rows, padded suffix, store hit) bucket — and the
decode step, a second that serves every page count the traffic reaches
(the hand-off and the store run small programs shaped by it), then a
fresh server that serves the mix's own traffic for
its ``ramp_s`` so the queue, the decode batch and the prefix store reach
steady state.  The window then runs for ``--seconds``; with ``--trace 1``
the profiler records it.  Afterwards the device's peak memory is read,
the server is freed, and a sample of the finished requests is checked
against the plain reference (``correctness.py``).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import pathlib
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from . import correctness, driver, model, traffic
from .compile_tally import CompileTally
from .driver import Record, now

HERE = pathlib.Path(__file__).resolve().parent
MAX_WAIT_S = 60.0          # after the close, for the window's first tokens


def load_cell(name: str) -> Dict[str, Any]:
    with open(HERE / "cells" / f"{name}.json") as f:
        return json.load(f)


@dataclasses.dataclass
class Run:
    """Everything a run observed, for the metric readers."""
    cfg: Dict[str, Any]
    mix: Dict[str, Any]
    cellfile: Dict[str, Any]
    window: tuple
    records: Dict[int, Record]
    spans: List[driver.Span]
    peaks: Dict[str, float]
    trace: Any = None                # trace.Trace of the window, if traced
    compiles_in_window: int = 0
    setup_s: float = 0.0
    memory_peak_bytes: Optional[int] = None

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    def window_records(self) -> List[Record]:
        return [r for r in self.records.values() if r.segment == "window"]


def _pow2s(lo: int, hi: int) -> List[int]:
    out, v = [], 1
    while v <= hi:
        if v >= lo:
            out.append(v)
        v <<= 1
    return out


def _orchestrator(pcfg, params, serving: Dict[str, Any], hw):
    from repro.serving.api import Server
    from repro.serving.engine import EngineConfig
    from repro.serving.orchestrator import Orchestrator, OrchestratorConfig

    ecfg = EngineConfig(max_len=serving["max_len"],
                        max_batch=serving["max_batch"],
                        block_size=serving["block_size"])
    ocfg = OrchestratorConfig(
        n_prefill=serving["n_prefill"], n_decode=serving["n_decode"],
        engine=ecfg, chunk_tokens=serving["chunk_tokens"],
        prefill_chunk=serving["prefill_chunk"], hw=hw)
    return Server(Orchestrator(pcfg, params, ocfg))


def warm_up(make_server: Callable, mix: Dict[str, Any], vocab: int,
            rng: np.random.Generator, log) -> None:
    """Run every prefill bucket the mix can reach through a throwaway
    server: for rows r in the powers of two up to ``prefill_chunk``, a
    batch of r distinct prompts per padded miss length (from the mix's
    shortest prompt up to a chunk), and per padded hit length (1 up to a
    chunk) r prompts whose first block the store already holds.  Each
    request decodes two tokens, so the decode step and the hand-off run
    too."""
    from repro.serving.request import Request

    s = mix["serving"]
    chunk, bs = s["chunk_tokens"], s["block_size"]
    rows = _pow2s(1, s["prefill_chunk"])
    shortest = min(mix["prompt"]["min"], chunk)
    misses = sorted(set(_pow2s(shortest, chunk))
                    | {1 << (shortest - 1).bit_length(), chunk})
    hits = sorted(set(_pow2s(1, chunk)) | {chunk})
    server = make_server()
    rid = 0

    def serve(prompts: List[np.ndarray]) -> None:
        nonlocal rid
        for p in prompts:
            server.submit(Request(rid=rid, arrival=0.0, prompt=p,
                                  max_new_tokens=2))
            rid += 1
        server.drain()

    def tokens(n: int) -> np.ndarray:
        return rng.integers(0, vocab, n, dtype=np.int32)

    t = now()
    for r in rows:
        for n in misses:
            serve([tokens(n) for _ in range(r)])
        bases = [tokens(bs) for _ in range(r)]
        serve(bases)
        for n in hits:
            serve([np.concatenate([b, tokens(n)]) for b in bases])
    log(f"warm-up: {len(rows)} row counts x ({len(misses)} miss + "
        f"{len(hits)} hit lengths) in {now() - t:.1f}s")
    del server
    gc.collect()


def warm_traffic(make_server: Callable, specs: List[traffic.Spec],
                 group_len, bs: int, max_total: int, shortest: int,
                 vocab: int, rng: np.random.Generator, log) -> None:
    """Serve the page counts the mix's requests reach, through a throwaway
    server: the hand-off, the store's publishing and binding and the page
    movers run small programs whose shapes follow a request's page count
    (and, on a store hit, its prefix's), which the bucket sweep does not
    reach.  One request per page count up to ``max_total`` tokens as a
    store miss (which also frees that many pages when it ends, as a long
    output does; none shorter than the mix's ``shortest`` prompt), then,
    once each prefix group is in the store, one per (group, page count) of
    ``specs`` as a hit.  Each decodes two tokens."""
    from repro.serving.request import Request

    misses = {p: min(max(p * bs - 1, shortest), max_total - 2)
              for p in range(1, -(-max_total // bs) + 1)}
    hits = {}
    for sp in specs:
        if sp.group >= 0:
            n = int(group_len[sp.group]) + sp.suffix
            hits.setdefault((sp.group, -(-n // bs)), sp.suffix)
    prefix = {g: rng.integers(0, vocab, int(group_len[g]), dtype=np.int32)
              for g in sorted({g for g, _ in hits})}
    server = make_server()
    rid = 0

    def serve(prompts) -> list:
        nonlocal rid
        reqs = []
        for p in prompts:
            reqs.append(Request(rid=rid, arrival=0.0, prompt=p,
                                max_new_tokens=2))
            server.submit(reqs[-1])
            rid += 1
        server.drain()
        return reqs

    def tokens(n: int) -> np.ndarray:
        return rng.integers(0, vocab, n, dtype=np.int32)

    t = now()
    serve([tokens(n) for n in misses.values()])
    serve(list(prefix.values()))
    reqs = serve([np.concatenate([prefix[g], tokens(s)])
                  for (g, _), s in hits.items()])
    n_hit = sum(1 for r in reqs if r.cached_tokens)
    log(f"warm-up traffic: {len(misses)} miss and {len(hits)} hit page "
        f"counts ({n_hit} hit the store) in {now() - t:.1f}s")
    del server
    gc.collect()


def load(cell: Dict[str, Any]) -> tuple:
    """(configuration, traffic mix, cell file) of a BENCHMARK.json cell."""
    return (model.load(cell["config"]), traffic.load(cell["traffic"]),
            load_cell(cell["name"]))


class Bench:
    """A configuration and mix set up on the device: weights from the
    seed and every prefill bucket compiled or loaded; ``warm`` then runs
    the page counts of the traffic to be served.  ``serve``
    then measures windows on fresh servers; ``reseed`` makes the weights
    of another seed, which the compiled programs serve unchanged."""

    def __init__(self, cfg: Dict[str, Any], mix: Dict[str, Any], seed: int,
                 *, hw=None, log=print):
        self.cfg, self.mix, self.hw, self.log = cfg, mix, hw, log
        self.pcfg = model.program_config(cfg)
        self.tally = CompileTally()
        self.reseed(seed)
        warm_up(self.make_server, mix, cfg["vocab_size"],
                np.random.default_rng([seed, 1]), log)

    def warm(self, rates: List[float], seconds: float) -> None:
        """Serve the page counts of the mix (``warm_traffic``), with the
        store hits of its schedules at ``rates`` with a ``seconds``
        window."""
        mix = self.mix
        gen = traffic.Traffic(mix, self.cfg["vocab_size"], self.seed)
        specs = [spec for rate in rates for _, spec, _ in gen.schedule(
            rate, float(mix["ramp_s"]), seconds, MAX_WAIT_S)]
        warm_traffic(self.make_server, specs, gen.group_len,
                     mix["serving"]["block_size"], int(mix["max_total"]),
                     int(mix["prompt"]["min"]), self.cfg["vocab_size"],
                     np.random.default_rng([self.seed, 3]), self.log)

    def reseed(self, seed: int) -> None:
        import jax
        self.params = None
        gc.collect()
        t = now()
        self.seed = seed
        self.params = model.make_weights(self.cfg, seed)
        jax.block_until_ready(self.params)
        self.log(f"weights: {model.n_params(self.cfg):,} params "
                 f"{self.cfg['dtype']} in {now() - t:.1f}s")

    def make_server(self):
        """A fresh server (1 prefill + 1 decode member on device 0)."""
        return _orchestrator(self.pcfg, self.params, self.mix["serving"],
                             self.hw)

    def serve(self, cellfile: Dict[str, Any], seconds: float,
              trace: bool = False,
              rate: Optional[float] = None) -> "Run":
        """Ramp for the mix's ``ramp_s``, then measure ``seconds``.  Frees
        the server before returning, so the device holds the weights
        only; ``Run.memory_peak_bytes`` is read before that."""
        import jax

        mix, seed = self.mix, self.seed
        spans = driver.Spans(annotate=trace)
        drv = driver.Driver(self.make_server(), spans)
        gen = traffic.Traffic(mix, self.cfg["vocab_size"], seed)
        ramp = float(mix["ramp_s"])
        t0 = now()
        window = (t0 + ramp, t0 + ramp + seconds)
        rid = 0

        def record(due: float, spec, segment: str) -> Record:
            nonlocal rid
            rid += 1
            req = traffic.make_request(rid, gen.prompt(spec), spec.output)
            return Record(rid, due, segment, req, req.prompt_len)

        state: Dict[str, Any] = {"built": len(self.tally.names)}

        def on_open():
            if trace:
                from . import trace as tr
                state["tracer"] = tr.Tracer()
                state["tracer"].start()
            state["compiles"] = self.tally.programs
            state["opened"] = len(self.tally.names)
            state["t_open"] = now()
            if trace:
                state["tracer"].open_window()

        def on_close():
            state["t_close"] = now()
            state["compiles"] = self.tally.programs - state["compiles"]
            state["closed"] = len(self.tally.names)
            if trace:
                state["tracer"].close_window()

        rate = float(cellfile["rate_per_s"] if rate is None else rate)
        sched = [record(t0 + off, spec, seg) for off, spec, seg in
                 gen.schedule(rate, ramp, seconds, MAX_WAIT_S)]
        driver.open_loop(drv, sched, window, MAX_WAIT_S,
                         on_open=on_open, on_close=on_close)
        spans.recording = False
        if trace:
            state["trace"] = state["tracer"].stop()
        t_open = state["t_open"]
        self.log(f"window opened {t_open - t0:.1f}s after the ramp began, "
                 f"{state['t_close'] - t_open:.1f}s long; "
                 f"{state['compiles']} programs compiled inside it")
        names = self.tally.names
        for part, lo, hi in (("ramp", "built", "opened"),
                             ("window", "opened", "closed")):
            built = [f"{n}{' (cache)' if hit else ''}"
                     for n, hit in names[state[lo]:state[hi]]]
            if built:
                self.log(f"programs built in the {part}: {', '.join(built)}")
        stats = jax.devices()[0].memory_stats() or {}
        run = Run(self.cfg, mix, cellfile, (t_open, state["t_close"]),
                  drv.records, list(spans.log), {},
                  compiles_in_window=state["compiles"])
        run.memory_peak_bytes = stats.get("peak_bytes_in_use")
        run.trace = state.get("trace")
        # free the program's state before the reference runs (a request's
        # clock holds the events, and they hold device arrays)
        for rec in drv.records.values():
            rec.req.clock = None
        drv.server = None
        del drv
        gc.collect()
        return run


def run_cell(seed: int, seconds: float, trace: bool,
             *, cfg: Dict[str, Any], mix: Dict[str, Any],
             cellfile: Dict[str, Any], t_process: float,
             peaks: Dict[str, float], hw=None, log=print) -> Dict:
    """One run; returns {"run": Run, "check": ...}.  ``hw`` names the part
    the program's virtual clock bills (None: the device's own; a CPU run
    must name one)."""
    bench = Bench(cfg, mix, seed, hw=hw, log=log)
    bench.warm([float(cellfile["rate_per_s"])], seconds)
    run = bench.serve(cellfile, seconds, trace)
    run.peaks = peaks
    run.setup_s = run.window[0] - t_process
    check = correctness.check(run, bench.params, seed, log=log)
    return {"run": run, "check": check}
