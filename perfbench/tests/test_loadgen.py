"""The one load generator with more than one client (harness/loadgen.py):
as many requests in flight as the mix has clients, none sent once the
window has closed, every one sent waited for, a failed one counted and
not raised, a later client's first request held back by the mix's stagger;
and the two readers of the concurrent cell on such a window."""

import os
import sys
import threading
import time
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from harness import cells, loadgen  # noqa: E402

METHODS = ("prove", "submit")


class Served:
    """Answers a request after `hold` seconds and counts how many it
    holds at once."""

    def __init__(self, hold=0.05, fail=()):
        self.hold, self.fail = hold, set(fail)
        self.inside = self.most = 0
        self.lock = threading.Lock()

    def client(self):
        return self

    def _call(self, method, params):
        assert method == METHODS[0]
        with self.lock:
            self.inside += 1
            self.most = max(self.most, self.inside)
        time.sleep(self.hold)
        with self.lock:
            self.inside -= 1
        if params["i"] in self.fail:
            raise RuntimeError("refused")
        return {"proof": params["i"]}


def window(served, clients, seconds=0.22, **mix):
    w = loadgen.Window(served, dict(mix, clients=clients,
                                    concurrency=clients),
                       lambda i: {"params": {"i": i}}, METHODS, seconds)
    return w.run()


@pytest.mark.parametrize("clients", [1, 2, 3])
def test_as_many_in_flight_as_clients_and_every_request_waited_for(clients):
    served = Served()
    w = window(served, clients)
    assert served.most == clients and served.inside == 0
    assert [s.index for s in w.sent] == list(range(len(w.sent)))
    assert len(w.sent) >= 2 * clients          # each client sent again
    assert len(w.ok) == len(w.sent)
    for s in w.sent:
        assert s.t_send - w.t_first < w.seconds     # none after the close
        assert s.t_done >= s.t_send + served.hold   # each was waited for
    assert w.t_last == max(s.t_done for s in w.sent)
    assert w.seconds <= w.wall_s < w.seconds + 2 * served.hold + 0.1


def test_two_clients_serve_twice_what_one_does_in_the_same_window():
    one, two = window(Served(), 1), window(Served(), 2)
    assert len(two.ok) >= 2 * len(one.ok) - 2
    prove_s = cells.load_plugin("metrics", "prove_s").read
    assert prove_s({"window": two}) == two.wall_s / len(two.ok)
    assert prove_s({"window": two}) < prove_s({"window": one})


def test_a_failed_request_is_counted_and_the_client_goes_on():
    w = window(Served(fail={1}), 2)
    assert [s.index for s in w.sent if s.error] == [1]
    assert "refused" in w.sent[1].error
    assert len(w.ok) == len(w.sent) - 1 >= 3


def test_a_staggered_client_starts_late_and_the_window_is_the_firsts():
    served = Served(hold=0.1)
    w = window(served, 2, seconds=0.25, stagger_s=0.05)
    first, second = w.sent[0], w.sent[1]
    assert first.t_send - w.t_first < 0.02
    assert 0.05 <= second.t_send - w.t_first < 0.09
    # the two loops stay out of phase by the stagger, and the window closes
    # by the first send's clock for both: 0, .05, .1, .15, .2 and no more
    assert [round(s.t_send - w.t_first, 2) for s in w.sent] \
        == [0.0, 0.05, 0.1, 0.15, 0.2]
    assert served.most == 2 and len(w.ok) == 5


def test_a_client_whose_turn_comes_after_the_close_sends_nothing():
    w = window(Served(), 2, seconds=0.04, stagger_s=0.1)
    assert [s.index for s in w.sent] == [0]
    assert w.wall_s >= 0.05


def recorded(waits, peak):
    sent = [SimpleNamespace(error=None, manifest={"queue_wait_s": q})
            for q in waits]
    sent.append(SimpleNamespace(error="refused", manifest=None))
    return {"window": SimpleNamespace(sent=sent), "memory_peak_bytes": peak}


def test_queue_wait_is_the_mean_over_the_served_jobs():
    read = cells.load_plugin("metrics", "queue_wait_s.pair").read
    assert read(recorded([0.001, 0.003, 26.5, 0.0], 1)) \
        == pytest.approx(26.504 / 4)
    assert read(recorded([], 1)) is None            # nothing served
    assert read(recorded([None, None], 1)) is None  # a manifest without it


def test_hbm_peak_is_the_runs_peak_in_gb_whatever_the_cell():
    read = cells.load_plugin("metrics", "hbm_peak_gb.serial").read
    assert read(recorded([0.0], 5_250_000_000)) == 5.25
    assert read(recorded([0.0], None)) is None      # XLA:CPU reports none


def job(t0, stretches):
    """A job's span tree from wall second `t0`: a root and, for each
    (start, end) of `stretches`, a `dispatch` and the `wait` after it."""
    def ev(name, a, b):
        return {"ph": "X", "name": name, "ts": int((t0 + a) * 1e6),
                "dur": int((b - a) * 1e6)}
    end = max(b for _, b in stretches) + 1.0
    spans = [ev("job", 0.0, end)]
    for a, b in stretches:
        spans += [ev("backend/msm_many/dispatch", a, a + 0.1),
                  ev("backend/msm_many/wait", a + 0.1, b)]
    return SimpleNamespace(error=None, manifest={}, spans=spans)


def test_no_inflight_is_the_windows_seconds_with_no_job_on_the_device():
    read = cells.load_plugin("metrics", "no_inflight_s.pair").read
    t = 1_790_000_000.0                      # wall clock, as `ts` has it
    # job A in flight 1-4 and 6-8, job B (2 s late) 3-7 and 9-10: together
    # 1-8 and 9-10 of a window of 12 s, so 4 s with neither, 2 s a proof
    a, b = job(t, [(1, 4), (6, 8)]), job(t + 2, [(1, 5), (7, 8)])
    failed = SimpleNamespace(error="refused", manifest=None, spans=[])
    w = SimpleNamespace(sent=[a, b, failed], t_first=t, wall_s=12.0)
    assert read({"window": w}) == pytest.approx(2.0, abs=1e-4)
    # one job alone: what `host_only_s` reads of it, plus the window's rest
    w = SimpleNamespace(sent=[a], t_first=t, wall_s=9.0)
    assert read({"window": w}) == pytest.approx(4.0, abs=1e-4)
    # a program with no such spans gives nothing to read
    bare = SimpleNamespace(error=None, manifest={}, spans=[])
    w = SimpleNamespace(sent=[bare], t_first=t, wall_s=9.0)
    assert read({"window": w}) is None
