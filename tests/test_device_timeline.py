"""The device timeline (observability/tracing.py, ISSUE 40): a mark at
every site that enqueues device work, one FIFO a process and one watcher
thread that stamps when each mark's outputs are ready, on the spans'
clock. Here: the stretch arithmetic against a fake clock, FIFO order
across two job threads, nothing without a trace, the watcher's exit, the
manifest's `device` block, `idle_by_span` on the hand-made job of
`perfbench/tests/recorded_spans.json`, and `chrome_trace`'s device track.
Last, the benchmark's readers of the timeline
(`perfbench/tests/test_timeline.py`, run here as tier-1 tests)."""

import collections
import importlib.util
import json
import os
import threading
import time

import pytest

from spectre_tpu.observability import manifest, tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "perfbench", "tests")


@pytest.fixture
def watcher(monkeypatch):
    """A fresh process watcher in place of the module's (`ready` records
    the outputs of each mark it takes, in order)."""
    taken = []
    w = tracing._Watcher(ready=taken.append)
    w.taken = taken
    monkeypatch.setattr(tracing, "_WATCHER", w)
    return w


# -- the arithmetic -----------------------------------------------------------

@pytest.mark.parametrize("enq,prev,want", [
    # the device idle when the first program went in: from it
    ((1.0, 2.0), 0.5, (1.0, 2.0)),
    # the previous mark still running: from its end
    ((1.0, 2.0), 1.5, (1.5, 2.0)),
    ((1.0, 2.0), 2.5, (2.5, 2.5)),
])
def test_stretch_starts_at_the_later_of_enqueue_and_previous_done(
        enq, prev, want):
    assert tracing.stretch(enq[0], enq[1], prev) == want


def test_watcher_stamps_in_order_against_a_fake_clock():
    dones = iter([3.0, 3.5, 6.0])
    w = tracing._Watcher(clock=lambda: next(dones), ready=lambda out: None)
    tr = tracing.Trace("fake")
    for kind, a, b in (("backend/ntt", 1.0, 2.0), ("backend/msm", 2.2, 2.4),
                       ("quotient/extend", 4.0, 5.0)):
        w.push(tracing._Mark(tr, kind, a, b, None))
    assert w.drain(tr, timeout=10)
    assert tr.device == [
        ("backend/ntt", 1.0, 2.0, 1.0, 2.0, 3.0),
        ("backend/msm", 2.2, 2.4, 3.0, 3.0, 3.5),
        ("quotient/extend", 4.0, 5.0, 4.0, 5.0, 6.0)]
    assert tr.pending == 0 and tr.interleaved == 0


def test_marks_whose_enqueues_meet_another_job_s_are_counted_on_both():
    dones = iter([3.0, 3.5, 4.0, 6.0])
    w = tracing._Watcher(clock=lambda: next(dones), ready=lambda out: None)
    a, b = tracing.Trace("a"), tracing.Trace("b")
    for tr, enq_first, enq_last in (
            (a, 1.0, 2.0),      # alone
            (b, 1.5, 2.5),      # began before a's last enqueue: both
            (b, 2.6, 2.8),      # after its own: nobody's
            (a, 3.2, 3.9)):     # after b's last enqueue: nobody's
        w.push(tracing._Mark(tr, "backend/ntt", enq_first, enq_last, None))
    assert w.drain(a, timeout=10) and w.drain(b, timeout=10)
    assert (a.interleaved, b.interleaved) == (1, 1)
    assert tracing.device_summary(b)["interleaved"] == 1


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
@pytest.mark.parametrize("error", [RuntimeError, ValueError])
def test_a_failed_wait_is_stamped_and_leaves_no_drain_waiting(error):
    """A deleted buffer (jax raises a RuntimeError) is stamped as done; an
    error no mark should hit is stamped too, ends the thread, and the next
    mark starts another."""
    def ready(out):
        if out == "bad":
            raise error("deleted")

    w = tracing._Watcher(ready=ready)
    tr = tracing.Trace("failing")
    w.push(tracing._Mark(tr, "backend/ntt", 0.0, 0.0, "bad"))
    th = w._thread
    assert w.drain(tr, timeout=10) and len(tr.device) == 1
    if error is ValueError:
        th.join(5)
        assert not th.is_alive() and w._thread is None
    w.push(tracing._Mark(tr, "backend/ntt", 0.0, 0.0, "good"))
    assert w.drain(tr, timeout=10) and len(tr.device) == 2


def test_fifo_order_is_enqueue_order_across_two_job_threads(watcher):
    pushed, order = [], threading.Lock()
    traces = {}

    def job(tid):
        with tracing.trace(f"job-{tid}") as tr:
            traces[tid] = tr
            for i in range(200):
                with tracing.span(f"backend/op{tid}/dispatch"):
                    with order:
                        tracing.on_device((tid, i))
                        pushed.append((tid, i))

    threads = [threading.Thread(target=job, args=(t,)) for t in (0, 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert watcher.taken == pushed
    assert {k for k, *_ in traces[0].device} == {"backend/op0"}
    assert {k for k, *_ in traces[1].device} == {"backend/op1"}
    # one device: the two jobs' stretches never overlap
    both = sorted((s[3], s[5]) for tr in traces.values() for s in tr.device)
    assert len(both) == 400
    for (_, done), (lo, _) in zip(both, both[1:]):
        assert lo >= done


def test_nothing_without_a_trace(watcher):
    assert tracing.active() is None
    assert tracing.on_device([1, 2]) is None
    assert watcher._thread is None and watcher.taken == []
    assert "device" not in tracing.summary(None)
    with tracing.trace("no-marks") as tr:
        with tracing.span("backend/ntt/dispatch"):
            pass
    assert "device" not in tracing.summary(tr)
    assert {e["ph"] for e in tracing.chrome_trace(tr)["traceEvents"]} \
        == {"X"}
    assert watcher._thread is None


def test_watcher_exits_when_the_fifo_stays_empty(watcher, monkeypatch):
    monkeypatch.setattr(tracing, "WATCH_IDLE_S", 0.05)
    for round_ in range(2):             # and a later mark starts another
        with tracing.trace(f"short-{round_}") as tr:
            with tracing.span("backend/ntt/dispatch"):
                tracing.on_device(round_)
            th = watcher._thread
            assert th is not None
        assert len(tr.device) == 1
        th.join(5)
        assert not th.is_alive()
        deadline = time.time() + 5
        while watcher._thread is not None and time.time() < deadline:
            time.sleep(0.01)
        assert watcher._thread is None


def test_a_real_array_and_the_manifest_s_device_block():
    import jax.numpy as jnp

    with tracing.trace("one-mark") as tr:
        with tracing.span("backend/ntt") as call:
            with tracing.span("backend/ntt/dispatch") as d:
                x = jnp.arange(1 << 12, dtype=jnp.uint32) * 3
                enq_last = tracing.on_device(x)
            with tracing.span("backend/ntt/wait"):
                int(x[-1])
    (kind, enq_first, last, lo, lo_lower, done), = tr.device
    assert (kind, enq_first, last) == ("backend/ntt", d.t0, enq_last)
    assert d.t0 <= lo <= lo_lower <= done and enq_last <= done
    assert call.t0 <= done
    man = manifest.build(job_id="j", method="m", trace=tr)
    dev = man["device"]
    assert set(dev) == {"busy_s", "idle_s", "busy_lower_s", "by_kind",
                        "by_kind_lower", "marks", "interleaved",
                        "idle_by_span"}
    assert dev["marks"] == 1 and dev["interleaved"] == 0
    assert set(dev["by_kind"]) == {"ntt"}
    assert dev["busy_s"] + dev["idle_s"] \
        == pytest.approx(tr.root.t1 - tr.root.t0, abs=2e-6)
    assert dev["busy_lower_s"] <= dev["busy_s"]
    json.dumps(man)


@pytest.mark.parametrize("kind,want", [
    ("backend/msm_many", "msm"), ("backend/msm_sharded", "msm"),
    ("ec/decode_points", "other"), ("backend/intt_many", "ntt"),
    ("backend/coset_lde_many", "ntt"), ("backend/ntt_sharded", "ntt"),
    ("quotient/extend", "quotient"), ("quotient/expressions", "quotient"),
    ("quotient/inverse", "quotient"), ("backend/intt_many/upload", "upload"),
    ("backend/msm_many/upload", "upload")])
def test_kinds_by_the_words_of_the_benchmark(kind, want):
    assert tracing.device_kind(kind) == want


# -- the recorded job ---------------------------------------------------------

def recorded_trace() -> tracing.Trace:
    """The hand-made job of recorded_spans.json as a span tree, with the
    twelve marks' stretches of recorded_device.json."""
    with open(os.path.join(RECORDED, "recorded_spans.json")) as f:
        events = json.load(f)["traceEvents"]
    base = events[0]["ts"]
    tr = tracing.Trace("recorded")
    tr.perf0, tr.started_at = 0.0, base / 1e6
    stack = []
    for e in events:
        s = tracing.Span(e["name"], (e["ts"] - base) / 1e6)
        s.t1 = s.t0 + e["dur"] / 1e6
        while stack and not (stack[-1].t0 <= s.t0
                             and s.t1 <= stack[-1].t1 + 1e-9):
            stack.pop()
        if stack:
            stack[-1].children.append(s)
        else:
            tr.root = s
        stack.append(s)
    marks = [  # kind, enq_first, enq_last, lo, lo_lower, done (ms)
        ("backend/intt", 105, 110, 105, 110, 125),
        ("backend/intt", 134, 135, 134, 135, 145),
        ("backend/msm", 180, 185, 180, 185, 300),
        ("backend/msm", 185, 190, 300, 300, 370),
        ("quotient/extend", 420, 430, 420, 430, 470),
        ("quotient/extend", 470, 480, 470, 480, 510),
        ("quotient/expressions", 480, 500, 510, 510, 520),
        ("quotient/expressions", 500, 530, 530, 530, 535),
        ("quotient/inverse", 535, 536, 536, 536, 580),
        ("backend/ntt", 615, 620, 615, 620, 626),
        ("backend/ntt", 635, 640, 635, 640, 642),
        ("backend/msm", 830, 840, 830, 840, 865)]
    tr.device = [(k,) + tuple(v / 1e3 for v in rest) for k, *rest in marks]
    return tr


def test_idle_charged_to_the_innermost_span_open():
    dev = tracing.device_summary(recorded_trace())
    assert dev["busy_s"] == pytest.approx(0.423)
    assert dev["idle_s"] == pytest.approx(0.577)
    assert dev["busy_lower_s"] == pytest.approx(0.372)
    assert dev["by_kind"] == pytest.approx(
        {"msm": 0.225, "ntt": 0.049, "quotient": 0.149})
    assert dev["by_kind_lower"] == pytest.approx(
        {"msm": 0.210, "ntt": 0.033, "quotient": 0.129})
    # by hand, gap by gap: [0, 105] is the witness's 100 and the intt's
    # first encode's 5; [642, 830] the ntt's last wait 3 and decode 5,
    # h_poly's own 150 and multiopen's 20 before the msm's encode 10; ...
    assert dict(dev["idle_by_span"]) == pytest.approx({
        "multiopen/h_poly": 0.160, "job/witness": 0.100,
        "prove/self_verify": 0.100, "prove/multiopen": 0.040,
        "prove/commit_advice": 0.020, "backend/msm/encode": 0.020,
        "quotient/extend/encode": 0.020, "backend/msm/decode": 0.020,
        "backend/msm/wait": 0.015, "quotient/wait": 0.011})
    # and what the benchmark's recorded manifest block says
    with open(os.path.join(RECORDED, "recorded_device.json")) as f:
        assert json.load(f)["manifest_device"] == dev


def test_chrome_trace_adds_only_a_device_track():
    tr = recorded_trace()
    with_track = tracing.chrome_trace(tr)["traceEvents"]
    tr.device = []
    plain = tracing.chrome_trace(tr)["traceEvents"]
    assert with_track[:len(plain)] == plain
    extra = with_track[len(plain):]
    assert collections.Counter(e["ph"] for e in extra) \
        == {"M": 1, "B": 12, "E": 12}
    assert extra[0]["args"] == {"name": "device"}
    assert {e["tid"] for e in extra} == {tracing.DEVICE_TID} != {0}
    b = [e for e in extra if e["ph"] == "B"]
    assert b[3]["name"] == "backend/msm"
    assert b[3]["ts"] == pytest.approx(plain[0]["ts"] + 300e3)
    assert b[3]["args"] == {"lower_s": 0.07, "after_enqueue_s": 0.18}


# -- the benchmark's readers of the timeline, in tier-1 -----------------------

def _load_perfbench_tests():
    path = os.path.join(RECORDED, "test_timeline.py")
    spec = importlib.util.spec_from_file_location("perfbench_test_timeline",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_pb = _load_perfbench_tests()
for _name in dir(_pb):
    if _name.startswith("test_"):
        globals()["test_perfbench_" + _name[len("test_"):]] = getattr(
            _pb, _name)
