"""Drives a whole run of the harness on the CPU (the configuration names
the platform, so the look for a chip passes on `cpu`): the real service at
the 2-validator `tiny` spec, k=13, CpuBackend. Once sound, and once for each
fault this kind of cell can have, planted under the timed path:

- an answer altered where it is produced (after the service's own verifier
  has seen it, so only the benchmark's reference can notice);
- verify-before-serve skipped (the guarantee the configuration states).

The sound run and the altered answer are driven through both traffic
mixes: in `pair` the two jobs run side by side and ONE of them is altered,
so the run has to tell the sound neighbour from the altered one. The
faults of training cells (state unchanged, half a batch, the exchange
between chips) have no counterpart in a one-chip prover. Slow: eleven
served proves of about a minute each."""

import io
import itertools
import json
import os
import sys
from contextlib import redirect_stdout

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.dirname(BENCH))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

TINY_DIGEST = "7746ea22347fd2f366cd8b43b787fc43260081ec0bcd1d83d79736ae45d23806"


@pytest.fixture(scope="module")
def bench_path(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("bench")
    with open(os.path.join(BENCH, "configs",
                           "committee-minimal32-k14.json")) as f:
        config = json.load(f)
    config.update(name="tiny-k13-test", platform="cpu", backend="cpu",
                  spec="tiny", k=13, vk_digest=TINY_DIGEST)
    config["spec_sizes"]["sync_committee_size"] = 2
    cfg_file = tmp / "tiny.json"
    cfg_file.write_text(json.dumps(config))
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"][0].update(name="tiny-k13-test", file=str(cfg_file))
    for w in bench["workloads"]:
        w["config"] = "tiny-k13-test"
    path = tmp / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return str(path)


# cell -> requests a window sends that closes a second after its last
# client's first turn (`stagger_s` in the mix): one a client
CELLS = {"cu-minimal32.serial": 1, "cu-minimal32.pair": 2}


def seconds_for(workload) -> float:
    with open(os.path.join(BENCH, "traffic",
                           workload.rsplit(".", 1)[1] + ".json")) as f:
        mix = json.load(f)
    return 1.0 + mix.get("stagger_s", 0.0) * (mix["clients"] - 1)


def one_run(bench_path, seed, workload="cu-minimal32.serial"):
    import run
    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed",
                       str(seed), "--seconds", str(seconds_for(workload)),
                       "--trace", "0"],
                      bench_path=bench_path)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(bench_path, workload):
    line = one_run(bench_path, 2**31 + 1, workload)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == CELLS[workload]
    assert set(line["metrics"]) == {"prove_s", "setup_s"}
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
def test_altered_answer_is_not_correct(bench_path, monkeypatch, workload):
    from spectre_tpu.prover_service import rpc
    sound = rpc.verified_prove
    served = itertools.count(1)            # `next` is safe from two jobs

    def altered(state, kind, args, heartbeat=None):
        proof, instances = sound(state, kind, args, heartbeat=heartbeat)
        if next(served) != 2:              # the warm-up is left sound, and
            return proof, instances        # in `pair` the neighbour too
        bad = bytearray(proof)
        bad[-70] ^= 1                      # a byte of W1's y
        return bytes(bad), instances

    monkeypatch.setattr(rpc, "verified_prove", altered)
    line = one_run(bench_path, 2**31 + 2, workload)
    assert line["correct"] is False
    assert line["checks"]["proofs_rejected_by_reference"][0] == 1
    assert line["failed"] == 1
    assert line["attempted"] == CELLS[workload]


def test_unverified_serve_is_not_correct(bench_path, monkeypatch):
    from spectre_tpu.prover_service import rpc

    def unverified(state, kind, args, heartbeat=None):
        return state.prove_committee(args, heartbeat=heartbeat)

    monkeypatch.setattr(rpc, "verified_prove", unverified)
    line = one_run(bench_path, 2**31 + 3)
    assert line["correct"] is False
    assert line["checks"]["served_without_verify"][0] == 1
