"""Profiler spans of the serving path (``serving/tracing.py``): a run
served under a profiler session records one span per decode iteration,
prefill wave and hand-off, nested and with the stats the docs list, and
serves the same tokens as a run with no session."""
import glob

import jax
import pytest
from jax.profiler import ProfileData

from conftest import TINY, TINY_ECFG
from repro.core.analytical import TPU_V5E
from repro.serving import tracing
from repro.serving.api import Server
from repro.serving.orchestrator import Orchestrator, OrchestratorConfig
from repro.serving.workload import WorkloadConfig, generate

DECODE_CHILDREN = ("decode.prepare", "decode.forward", "decode.sync",
                   "decode.commit")
PREFILL_CHILDREN = ("prefill.match", "prefill.stage", "prefill.forward",
                    "prefill.extract")


def _serve(params, trace_dir=None):
    """Serve a prefix-sharing workload (long prompts chunk across waves)
    through ``Server``; record, per request, the pages its decode slot
    holds right after the hand-off, and each wave's record."""
    reqs = generate(WorkloadConfig(
        kind="synthetic", rps=500.0, n_requests=6,
        vocab_size=TINY.vocab_size, max_new_tokens=5, prefix_share=0.9,
        n_prefix_groups=1, seed=13, prompt_len_lo=16, prompt_len_hi=40))
    orch = Orchestrator(TINY, params, OrchestratorConfig(
        hw=TPU_V5E, n_prefill=1, n_decode=1, migration=False,
        engine=TINY_ECFG, chunk_tokens=16))
    dec = orch.decode_members()[0].decode
    pre = orch.prefill_members()[0].prefill
    slot_pages, waves = {}, []
    insert, prefill_waves = dec.insert, pre.prefill_waves

    def recording_insert(req, state, first_token, shared_pages=None):
        slot = insert(req, state, first_token, shared_pages=shared_pages)
        slot_pages[req.rid] = len(dec.slot_pages(slot))
        return slot

    def recording_waves(*a, **kw):
        for wave in prefill_waves(*a, **kw):
            waves.append(wave)
            yield wave

    dec.insert, pre.prefill_waves = recording_insert, recording_waves
    server = Server(orch)
    for r in reqs:
        server.submit(r)
    if trace_dir is None:
        server.drain()
    else:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(trace_dir, profiler_options=opts):
            server.drain()
    return {"orch": orch, "reqs": reqs, "decode_iters": dec.decode_iters,
            "slot_pages": slot_pages, "waves": waves}


def _host_events(trace_dir):
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                         dict(ev.stats) if ev.name.startswith("serve.")
                         else {}) for ev in line.events]
    return out


@pytest.fixture(scope="module")
def runs(tiny_params, tmp_path_factory):
    plain = _serve(tiny_params)
    d = tmp_path_factory.mktemp("profile")
    traced = _serve(tiny_params, str(d))
    return plain, traced, _host_events(str(d))


def _spans(events, name):
    return [e for e in events if e[0] == "serve." + name]


def _inside(events, outer, name):
    return [e for e in _spans(events, name)
            if outer[1] <= e[1] and e[2] <= outer[2]]


def test_span_is_the_shared_null_context_without_a_session():
    sp = tracing.span("decode.step", rows=3)
    assert sp is tracing.OFF and tracing.span("x") is tracing.OFF
    with sp as inner:
        inner.set_metadata(tokens=1)


def test_tokens_identical_with_and_without_a_session(runs):
    plain, traced, _ = runs
    assert [r.generated for r in traced["reqs"]] \
        == [r.generated for r in plain["reqs"]]
    assert all(r.generated for r in traced["reqs"])


def test_one_decode_step_span_per_iteration_with_its_children(runs):
    _, traced, events = runs
    steps = _spans(events, "decode.step")
    assert len(steps) == traced["decode_iters"] > 0
    for step in steps:
        assert 1 <= step[3]["rows"] <= TINY_ECFG.max_batch
        for child in DECODE_CHILDREN:
            assert len(_inside(events, step, child)) == 1, child
    for child in DECODE_CHILDREN:
        assert len(_spans(events, child)) == len(steps)


def test_one_prefill_wave_span_per_wave(runs):
    _, traced, events = runs
    waves = sorted(_spans(events, "prefill.wave"), key=lambda e: e[1])
    assert len(waves) == len(traced["waves"]) > 1
    assert any(w["resumed"] for w in traced["waves"])   # chunks resumed
    for span, wave in zip(waves, traced["waves"]):
        stats = span[3]
        assert stats == {"rows": wave["rows"],
                         "padded_len": wave["padded_len"],
                         "tokens": wave["tokens"],
                         "resumed": wave["resumed"],
                         "hit": int(wave["hit"])}
        for child in PREFILL_CHILDREN:
            assert len(_inside(events, span, child)) == 1, child
    # publishes nest inside the extraction of the rows they publish
    for pub in _spans(events, "prefill.publish"):
        assert any(e[1] <= pub[1] and pub[2] <= e[2]
                   for e in _spans(events, "prefill.extract"))


def test_stage_and_publish_count_the_pages_they_move(runs):
    """A paged hit wave's stage span counts the store pages it copied in
    pool to pool (``pages_in``); each publish span counts the blocks it
    published (``blocks``)."""
    _, traced, events = runs
    store = traced["orch"].store
    waves = sorted(_spans(events, "prefill.wave"), key=lambda e: e[1])
    pages_in = 0
    for span, wave in zip(waves, traced["waves"]):
        stage, = _inside(events, span, "prefill.stage")
        if wave["hit"]:
            pages_in += stage[3]["pages_in"]
        else:
            assert "pages_in" not in stage[3]
    assert 0 < pages_in <= store.stats.hit_blocks
    blocks = [e[3]["blocks"] for e in _spans(events, "prefill.publish")]
    assert min(blocks) >= 1 and sum(blocks) >= store.stats.inserts > 0


def test_one_handoff_span_per_request(runs):
    _, traced, events = runs
    handoffs = _spans(events, "handoff")
    by_rid = {e[3]["rid"]: e for e in handoffs}
    assert len(handoffs) == len(by_rid) == len(traced["reqs"])
    assert set(by_rid) == {r.rid for r in traced["reqs"]}
    for rid, span in by_rid.items():
        stats = span[3]
        assert stats["pages_bound"] + stats["pages_moved"] \
            == traced["slot_pages"][rid]
        assert stats["bytes_moved"] > 0
        for child in ("handoff.bind", "handoff.wait", "handoff.insert"):
            assert len(_inside(events, span, child)) == 1, child
    summary = traced["orch"].summary()
    assert summary["pages_bound"] \
        == sum(e[3]["pages_bound"] for e in handoffs) > 0
    assert summary["pages_moved"] \
        == sum(e[3]["pages_moved"] for e in handoffs)
    assert summary["handoff_bytes_moved"] \
        == sum(e[3]["bytes_moved"] for e in handoffs)


def test_event_spans_and_program_names(runs):
    _, traced, events = runs
    kinds = {e[0] for e in events if e[0].startswith("serve.event.")}
    assert {"serve.event.arrival", "serve.event.prefill",
            "serve.event.prefill_done", "serve.event.decode_done"} <= kinds
    assert len(_spans(events, "event.decode_done")) \
        == traced["decode_iters"]
    # each kind of forward dispatches under its own program name
    names = {e[0] for e in events}
    for fwd in ("prefill", "prefill_prefix", "decode"):
        assert f"PjitFunction({fwd})" in names, fwd
