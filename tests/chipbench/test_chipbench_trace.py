"""The trace reduction, on a trace recorded on a TPU v5e and on small
hand-made traces."""
import gzip
import pathlib
import shutil
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import trace as tr  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """Three calls of the page-fused decode kernel (8 rows x 64 pages,
    granite-moe-3b-a800m's attention widths) inside one annotated host
    span, profiled on one TPU v5e."""
    d = tmp_path_factory.mktemp("trace")
    with gzip.open(DATA / "paged_decode_v5e.xplane.pb.gz") as src, \
            open(d / "probe.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.read(str(d))


def test_recorded_clock_offset_from_completion_callbacks(recorded):
    # run 155 ends at 42.920351 ms on the device clock and its completion
    # callback starts at 44.624589 ms on the host's: the smallest lag
    assert recorded.clock_offset == pytest.approx(1.704238e-3, abs=1e-9)
    # on the host's clock every program starts after the span that
    # dispatched it opened
    (name, lo, hi), = recorded.spans
    assert name == "probe"
    assert recorded.modules["start"].min() > lo


def test_recorded_kernel_found_by_signature(recorded):
    kernel = recorded.ops["name"][recorded.ops["kernel"]]
    assert list(kernel) == ["paged_decode_attention"] * 3   # one per call
    # the program's other custom call is no Pallas kernel
    assert "custom-call" in set(recorded.ops["name"])
    assert not tr.is_page_fused(
        '%custom-call = bf16[513,16,8,64] custom-call(bf16[513,4,8,64] '
        '%slice-done), custom_call_target="ConcatBitcast"')


def test_recorded_busy_and_breakdown(recorded):
    # no "window" span: the window is the span of the device ops
    assert recorded.window_s() == pytest.approx(
        recorded.ops["end"].max() - recorded.ops["start"].min())
    busy = recorded.busy_s()
    assert 0 < busy <= recorded.window_s()
    ops = dict(recorded.breakdown()["device_ops"])
    assert max(ops, key=ops.get) == "paged_decode_attention"
    k = recorded.ops["kernel"]
    assert ops["paged_decode_attention"] == pytest.approx(
        float(np.sum(recorded.ops["end"][k] - recorded.ops["start"][k])))


def test_union_length_merges_overlaps():
    s = np.array([0.0, 1.0, 1.5, 5.0, 5.0])
    e = np.array([2.0, 1.2, 3.0, 6.0, 5.5])
    assert tr.union_length(s, e) == pytest.approx(4.0)
    assert tr.union_length(np.zeros(0), np.zeros(0)) == 0.0


def test_idle_gaps_inside_window():
    s = np.array([1.0, 2.0, 6.0])
    e = np.array([3.0, 2.5, 7.0])
    assert tr.idle_gaps(s, e, 0.0, 10.0) == [(0.0, 1.0), (3.0, 6.0),
                                              (7.0, 10.0)]


def _synthetic():
    """Two decode steps and a prefill wave; device programs start inside
    or after the spans that dispatched them."""
    spans = [("window", 0.0, 10.0), ("step", 0.0, 4.0),
             ("decode_step", 0.5, 3.5), ("step", 4.0, 9.0),
             ("prefill_wave", 4.2, 5.0), ("decode_step", 5.5, 8.5)]
    mods = {"start": np.array([1.0, 4.4, 6.0]),
            "end": np.array([3.0, 6.0, 8.0])}
    ops = {"start": np.array([1.0, 2.0, 4.4, 5.0, 6.0, 7.0]),
           "end": np.array([2.0, 3.0, 5.0, 6.0, 7.0, 8.0]),
           "name": np.array(["fusion", "k", "fusion", "fusion", "fusion",
                             "k"], object),
           "kernel": np.array([False, True, False, False, False, True]),
           "device": np.zeros(6, np.int64)}
    return tr.Trace(ops, mods, spans, 1)


def test_device_time_follows_the_span_open_at_program_start():
    t = _synthetic()
    decode = [s for s in t.spans if s[0] == "decode_step"]
    prefill = [s for s in t.spans if s[0] == "prefill_wave"]
    # the prefill program runs past its span's end: all of it is prefill
    assert t.span_ops(prefill) == pytest.approx(1.6)
    assert t.span_ops(decode) == pytest.approx(4.0)
    assert t.span_ops(decode, kernel=True) == pytest.approx(2.0)
    assert t.busy_s() == pytest.approx(5.6)


def test_breakdown_leaves_out_containers():
    ops = {"start": np.array([0.0, 0.1, 0.5, 2.0]),
           "end": np.array([1.0, 0.4, 0.9, 2.5]),
           "name": np.array(["while", "fusion", "fusion", "copy"], object),
           "kernel": np.zeros(4, bool), "device": np.zeros(4, np.int64)}
    t = tr.Trace(ops, {"start": np.zeros(0), "end": np.zeros(0)}, [], 1)
    assert dict(t.breakdown()["device_ops"]) == pytest.approx(
        {"fusion": 0.7, "copy": 0.5})
    assert t.busy_s() == pytest.approx(1.5)


def test_idle_gaps_named_by_innermost_open_span():
    # idle: [0, 1), [3, 4.4) and [8, 10), cut where spans open and close
    gaps = dict(_synthetic().breakdown()["idle_gaps"])
    assert gaps == pytest.approx({"step": 0.5 + 0.5 + 0.2 + 0.5,
                                  "decode_step": 0.5 + 0.5 + 0.5,
                                  "prefill_wave": 0.2,
                                  "no span open": 1.0})
