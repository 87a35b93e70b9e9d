"""The arithmetic on the program's spans (harness/spans.py): in-flight
stretches, their union by kind, self time, host-only seconds and the
profiler-clock share, each worked out by hand on the small recorded span
tree and rows kept beside this file (`recorded_spans.json`, see its
`about` keys). Times there are whole milliseconds, so sums are exact to
rounding."""

import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from harness import spans  # noqa: E402

with open(os.path.join(HERE, "recorded_spans.json")) as _f:
    REC = json.load(_f)
SENT = SimpleNamespace(error=None, spans=REC["traceEvents"], manifest={
    "transfer_bytes": {"h2d": 212992, "d2h": 33152}})


def ctx_of(*sent):
    return {"window": SimpleNamespace(sent=list(sent)), "trace": None,
            "config": {"name": "none"}}


def test_in_flight_stretches_in_order():
    got = [(round(a, 6), round(b, 6), call)
           for a, b, call in spans.in_flight(spans.events(SENT))]
    assert got == [
        (0.105, 0.130, "backend/intt"),     # first crossing
        (0.134, 0.150, "backend/intt"),     # second crossing
        (0.180, 0.380, "backend/msm"),      # two dispatches, one wait
        (0.420, 0.590, "quotient"),         # first LDE dispatch .. the read
        (0.615, 0.630, "backend/ntt"),
        (0.635, 0.645, "backend/ntt"),
        (0.830, 0.870, "backend/msm"),
    ]


def test_a_wait_without_a_dispatch_counts_from_its_own_start():
    evs = [("backend/msm/wait", 1.0, 3.0), ("backend/msm/dispatch", 4.0, 4.5)]
    assert spans.in_flight(evs) == [(1.0, 3.0, "backend/msm")]


def test_kinds_by_the_call_s_name():
    assert spans.is_kind("backend/intt_many/wait", spans.NTT_WORDS)
    assert spans.is_kind("backend/coset_lde_many", spans.NTT_WORDS)
    assert spans.is_kind("backend/msm_sharded/encode", spans.MSM_WORDS)
    assert not spans.is_kind("backend/msm/wait", spans.NTT_WORDS)
    assert not spans.is_kind("quotient/wait", spans.NTT_WORDS)
    assert not spans.is_kind("prove/multiopen", spans.MSM_WORDS)


def test_per_layer_readings_of_the_recorded_job():
    ctx = ctx_of(SENT)
    assert spans.inflight_seconds(ctx, spans.MSM_WORDS) \
        == pytest.approx(0.200 + 0.040)
    assert spans.inflight_seconds(ctx, spans.NTT_WORDS) \
        == pytest.approx(0.025 + 0.016 + 0.015 + 0.010)
    # encode + decode: msm 10+10 and 10+10; intt 5+4+10, ntt 5+5+5
    assert spans.host_stage_seconds(ctx, spans.MSM_WORDS) \
        == pytest.approx(0.040)
    assert spans.host_stage_seconds(ctx, spans.NTT_WORDS) \
        == pytest.approx(0.019 + 0.015)
    assert spans.named_seconds(ctx, "quotient/wait") == pytest.approx(0.055)
    assert spans.named_seconds(ctx, "job/witness") == pytest.approx(0.100)
    assert spans.transfer_mb(ctx) == pytest.approx(0.246144)


def test_host_only_is_the_root_outside_every_stretch():
    # 1 s less 25+16+200+170+15+10+40 ms in flight
    assert spans.host_only_s(spans.events(SENT)) == pytest.approx(0.524)
    assert spans.per_proof(ctx_of(SENT), spans.host_only_s) \
        == pytest.approx(0.524)


def test_self_time_is_the_span_less_what_covers_it():
    evs = spans.events(SENT)
    backend = lambda n: n.startswith("backend/")     # noqa: E731
    # 300 ms of multiopen less the ntt's 40 and the msm's 60 (stage spans
    # inside the calls add nothing to the union)
    assert spans.self_s(evs, "prove/multiopen", backend) \
        == pytest.approx(0.200)
    assert spans.self_s(evs, "prove/commit_advice", backend) \
        == pytest.approx(0.300 - 0.060 - 0.220)
    assert spans.self_s(evs, "prove/self_verify", backend) \
        == pytest.approx(0.100)
    assert spans.self_s(evs, "no/such", backend) is None


def test_longest_unnamed_stretch_of_each_span():
    top = spans.unnamed(spans.events(SENT))
    # multiopen/h_poly: 200 ms with one 40 ms child 10 ms in -> 150 ms left
    assert top[0] == (pytest.approx(0.150), "multiopen/h_poly")
    by_name = {}
    for sec, name in top:
        by_name.setdefault(name, sec)
    assert by_name["job"] == pytest.approx(0.0)       # phases back to back
    assert "prove/self_verify" not in by_name         # a leaf names itself
    assert by_name["prove/quotient"] == pytest.approx(0.005)
    assert by_name["backend/msm"] == pytest.approx(0.0)


def test_a_program_without_the_spans_gives_nothing_to_read():
    old = SimpleNamespace(error=None, manifest={"phase_seconds": {}}, spans=[
        e for e in REC["traceEvents"]
        if e["name"] == "job" or e["name"].startswith("prove/")])
    ctx = ctx_of(old)
    assert spans.inflight_seconds(ctx, spans.MSM_WORDS) is None
    assert spans.host_stage_seconds(ctx, spans.NTT_WORDS) is None
    assert spans.named_seconds(ctx, "quotient/wait") is None
    assert spans.per_proof(ctx, spans.host_only_s) is None
    assert spans.per_proof(ctx, lambda evs: spans.self_s(
        evs, "prove/multiopen", lambda n: n.startswith("backend/"))) is None
    assert spans.transfer_mb(ctx) is None
    assert spans.replay_inflight_device_pct(ctx, spans.NTT_WORDS) is None
    assert spans.per_proof(ctx_of(), spans.host_only_s) is None


def test_device_share_of_the_in_flight_annotations():
    rows = [tuple(r) for r in REC["rows"]]
    # 40 us in flight; 5 + 20 + 3 us of module events inside them
    assert spans.inflight_device_pct(rows, spans.NTT_WORDS) \
        == pytest.approx(100 * 28 / 40)
    assert spans.inflight_device_pct(rows, spans.MSM_WORDS) \
        == pytest.approx(100 * 30 / 40)
    host_only = [r for r in rows if not r[0].startswith("/device")]
    assert spans.inflight_device_pct(host_only, spans.NTT_WORDS) is None
    assert spans.inflight_device_pct(rows, ("lde",)) is None


def test_device_share_cannot_pass_100():
    rows = [("/host:CPU", "t", "backend/ntt/dispatch", 0, 10),
            ("/host:CPU", "t", "backend/ntt/wait", 10, 90),
            ("/device:TPU:0", "XLA Modules", "jit_a(1)", -50, 100),
            ("/device:TPU:0", "XLA Modules", "jit_b(2)", 20, 500)]
    assert spans.inflight_device_pct(rows, spans.NTT_WORDS) \
        == pytest.approx(100.0)


def test_every_new_metric_is_a_file_and_an_entry():
    from harness import cells
    cell = cells.load_cell("cu-minimal32.serial")
    names = [m["name"] for m in cell.per_layer]
    for name in ("msm_inflight_s", "msm_host_s", "ntt_inflight_s",
                 "ntt_host_s", "quotient_wait_s", "host_only_s",
                 "multiopen_self_s", "witness_build_s", "transfer_mb",
                 "ntt_inflight_device_pct"):
        assert name in names
        value = cells.load_plugin("metrics", name).read(ctx_of(SENT))
        assert (value is None) == (name == "ntt_inflight_device_pct"), name
