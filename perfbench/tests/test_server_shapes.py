"""The whole of a run but the program: `run.main` on the CPU with a fake
server shape and a fake circuit in the places where `cells.load_plugin`
finds servers/<shape>.py, requests/<circuit>.py and reference/<circuit>.py.
The run looks for the device before it boots the shape, drives the cell's
own traffic through it, and a wrong answer comes out as not correct."""

import io
import json
import os
import sys
import threading
import time
from contextlib import redirect_stdout
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import pytest  # noqa: E402

import run  # noqa: E402
from harness import cells, device  # noqa: E402

MANIFEST = {"compile": {"count": 0, "seconds": 0.0},
            "phase_seconds": {"prove/quotient": 0.01}, "queue_wait_s": 0.0}


class Client:
    def __init__(self, served):
        self.served = served

    def _call(self, method, params):
        s = self.served
        with s.lock:
            s.inside += 1
            s.most = max(s.most, s.inside)
        time.sleep(0.03)
        with s.lock:
            s.inside -= 1
            s.verified += 1
        return {"proof": "0x00", "answers": params["n"]}

    def _call_shedding(self, method, params):
        return {"job_id": f"job-{params['n']}"}

    def get_manifest(self, job_id):
        return MANIFEST

    def get_trace(self, job_id):
        return {"traceEvents": []}


class Served:
    zero_counters = ("fell_back",)
    url = "fake://"

    def __init__(self, log):
        self.log = log
        self.lock = threading.Lock()
        self.inside = self.most = self.verified = 0

    def client(self):
        return Client(self)

    def counters(self):
        return {"proofs_verified": self.verified, "fell_back": 0}

    def backend(self):
        return SimpleNamespace()

    def key_ready(self):
        return True

    def verifying_key(self):
        return {}

    def close(self):
        self.log.append(("close",))


def shape(log):
    """A module of servers/, as `cells.load_plugin` would return it."""
    def boot(config, traffic, paths):
        log.append(("boot", traffic["clients"]))
        return Served(log)

    return SimpleNamespace(boot=boot)


class Reference:
    vk_ok = True

    def __init__(self, config, vk):
        pass

    def check(self, request, result):
        return None if result["answers"] == request["params"]["n"] \
            else "another request's answer"


PLUGINS = {
    "requests": SimpleNamespace(
        METHOD="prove", SUBMIT_METHOD="submit",
        make=lambda config, seed, i: {"params": {"n": f"{seed}/{i}"}}),
    "reference": SimpleNamespace(Reference=Reference),
}


@pytest.fixture
def bench_path(tmp_path, monkeypatch):
    """A BENCHMARK.json whose one configuration names a fake circuit; the
    cells, the traffic mixes and the metric readers are the benchmark's
    own."""
    config = {"name": "fake-shape-test", "platform": "cpu", "circuit": "fake",
              "server": "fake", "pinning": None}
    (tmp_path / "config.json").write_text(json.dumps(config))
    with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [dict(bench["configs"][0], name="fake-shape-test",
                             file=str(tmp_path / "config.json"))]
    for w in bench["workloads"]:
        w["config"] = "fake-shape-test"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for var in ("BUILD_DIR", "PARAMS_DIR"):      # workdir.prepare sets them
        monkeypatch.setenv(var, os.environ.get(var, ""))
    return str(tmp_path / "BENCHMARK.json")


def install(monkeypatch) -> list:
    """Puts the fake shape and circuit where `cells.load_plugin` finds
    them and a CPU where the run looks for its device; returns the log
    that both write."""
    log: list = []
    plugins = dict(PLUGINS, servers=shape(log))
    real = cells.load_plugin
    monkeypatch.setattr(cells, "load_plugin", lambda kind, name:
                        plugins.get(kind) or real(kind, name))

    def looked(platform, chips):
        log.append(("looked", platform, chips))
        return {"platform": platform, "kind": "cpu", "count": 1}

    monkeypatch.setattr(device, "require_platform", looked)
    monkeypatch.setattr(device, "memory_peak_bytes", lambda: None)
    load = cells.load_cell

    def quick(workload, bench_path=None):
        """The cell as it is, its clients' stagger cut to the fake
        service's pace (a request takes 0.03 s here, not 26)."""
        cell = load(workload, bench_path)
        if "stagger_s" in cell.traffic:
            cell.traffic["stagger_s"] = 0.015
        return cell

    monkeypatch.setattr(cells, "load_cell", quick)
    return log


def one_run(bench_path, workload, seconds="0.1"):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(2**31 + 5),
                       "--seconds", seconds, "--trace", "0"],
                      bench_path=bench_path)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", ["cu-minimal32.serial",
                                      "cu-minimal32.pair"])
def test_a_run_looks_for_the_device_then_boots_the_cells_shape(
        bench_path, monkeypatch, workload):
    log = install(monkeypatch)
    traffic = cells.load_cell(workload, bench_path).traffic
    line = one_run(bench_path, workload, "0.2")
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["kind"] == "cpu"
    assert line["attempted"] >= 2 * traffic["clients"]
    assert set(line["metrics"]) == {"prove_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert log[:2] == [("looked", "cpu", 1), ("boot", traffic["clients"])]
    assert log[-1] == ("close",)


def test_a_wrong_answer_through_the_fake_shape_is_not_correct(bench_path,
                                                              monkeypatch):
    install(monkeypatch)
    sound = Client._call
    monkeypatch.setattr(Client, "_call", lambda self, method, params: dict(
        sound(self, method, params), answers="another request's"))
    line = one_run(bench_path, "cu-minimal32.serial")
    assert line["correct"] is False
    assert line["checks"]["proofs_rejected_by_reference"][0] \
        == line["attempted"] == line["failed"]
