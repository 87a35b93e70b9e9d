"""Output-integrity layer (ISSUE 9): verify-before-serve, artifact
scrubber, readiness self-check.

Acceptance pins:
  * an injected `proof.bytes:corrupt` bit-flip on a device prove is
    CAUGHT by self-verify, retried on the CPU backend, and the served
    proof is byte-identical to a clean CPU prove (digest-pinned);
  * with SPECTRE_SELF_VERIFY=off the same fault is served uncaught (the
    negative pin proving the layer is load-bearing) and the
    `prove/self_verify` span never opens;
  * the scrubber quarantines a hand-corrupted result file and removes a
    compaction-orphaned manifest without touching live jobs' artifacts.

Seconds-scale (toy K=6 circuit, CPU JAX) — runs in the default tier and
via `make test-faults`.
"""

import hashlib
import json
import os
import random
import subprocess
import sys
import time

import pytest

from spectre_tpu.utils import faults
from spectre_tpu.utils.health import HEALTH

RUN_SLOW = os.environ.get("RUN_SLOW") == "1"


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# toy prover state: REAL prove + REAL verify on the K=6 circuit
# ---------------------------------------------------------------------------

K = 6


def _toy_proof_setup():
    from spectre_tpu.plonk.constraint_system import Assignment, CircuitConfig
    from spectre_tpu.plonk.keygen import keygen
    from spectre_tpu.plonk.srs import SRS

    cfg = CircuitConfig(k=K, num_advice=1, num_lookup_advice=1, num_fixed=1,
                        lookup_bits=4)
    n = cfg.n
    x_w, y_w = 7, 3
    out = x_w + x_w * y_w
    advice = [[0] * n]
    advice[0][0:5] = [x_w, x_w, y_w, out, 5]
    selectors = [[0] * n]
    selectors[0][0] = 1
    lookup = [[0] * n]
    lookup[0][0] = x_w
    fixed = [[0] * n]
    fixed[0][0] = 5
    copies = [
        ((cfg.col_instance(0), 0), (cfg.col_gate_advice(0), 3)),
        ((cfg.col_fixed(0), 0), (cfg.col_gate_advice(0), 4)),
        ((cfg.col_gate_advice(0), 0), (cfg.col_lookup_advice(0), 0)),
    ]
    srs = SRS.unsafe_setup(K)
    pk = keygen(srs, cfg, fixed, selectors, copies)
    asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
    return pk, srs, asg, out


def _seeded_rng():
    from spectre_tpu.fields import bn254
    rnd = random.Random(0xFA17)
    return lambda: rnd.randrange(bn254.R)


@pytest.fixture(scope="module")
def toy():
    return _toy_proof_setup()


@pytest.fixture(scope="module")
def clean_cpu_proof(toy):
    from spectre_tpu.plonk import backend as B
    from spectre_tpu.plonk.prover import prove
    pk, srs, asg, _ = toy
    return prove(pk, srs, asg, B.get_backend("cpu"),
                 blinding_rng=_seeded_rng())


class _ToyState:
    """ProverState stand-in with real prove/verify on the toy circuit.

    Proving always runs on CPU with seeded blinding (so bytes are
    reproducible); the `backend` kwarg is RECORDED, which is what the
    SDC-retry tests assert on."""

    def __init__(self, toy, jobs=None):
        self.pk, self.srs, self.asg, self.out = toy
        self.jobs = jobs
        self.prove_backends = []      # backend arg per prove call

    def prove_step(self, args, heartbeat=None, backend=None):
        from spectre_tpu.plonk import backend as B
        from spectre_tpu.plonk.prover import prove
        self.prove_backends.append(getattr(backend, "name", None))
        proof = prove(self.pk, self.srs, self.asg, B.get_backend("cpu"),
                      blinding_rng=_seeded_rng())
        return proof, [self.out]

    def verify_proof(self, kind, proof, instances):
        from spectre_tpu.plonk.verifier import verify
        return verify(self.pk.vk, self.srs, [instances], proof)


def _self_verify_count():
    from spectre_tpu.observability import metrics
    return metrics.PHASE_SECONDS.labels(
        phase="prove/self_verify").snapshot()["count"]


# ---------------------------------------------------------------------------
# verify-before-serve
# ---------------------------------------------------------------------------

class TestVerifiedProve:
    def test_clean_prove_verifies_and_serves(self, toy, clean_cpu_proof):
        from spectre_tpu.prover_service import selfverify as SV
        st = _ToyState(toy)
        v0 = HEALTH.get("proofs_verified")
        proof, inst = SV.verified_prove(st, "step", None)
        assert proof == clean_cpu_proof
        assert inst == [st.out]
        assert HEALTH.get("proofs_verified") == v0 + 1
        assert st.prove_backends == [None]

    def test_verifier_spans_under_self_verify(self, toy):
        """What is left of the host verifier has a name: its four stages
        are children of `prove/self_verify`, and `verify/accumulate` says
        how many (commitment, scalar) pairs its one MSM took."""
        from spectre_tpu.observability import tracing
        from spectre_tpu.prover_service import selfverify as SV
        with tracing.trace("self-verify-spans") as tr:
            SV.verified_prove(_ToyState(toy), "step", None)
        phase, = [s for s in tr.root.children
                  if s.name == "prove/self_verify"]
        assert [s.name for s in phase.children] == [
            "verify/replay", "verify/identity", "verify/accumulate",
            "verify/pairing"]
        assert all(s.seconds() is not None for s in phase.children)
        pk = toy[0]
        opened = len({key for key, _ in pk.vk.query_plan()})
        # every opened polynomial, then G, W1 and W2
        assert phase.children[2].meta == {"points": opened + 3}

    def test_bitflip_caught_cpu_retry_byte_identical(self, toy,
                                                     clean_cpu_proof):
        """THE acceptance pin: an SDC'd device prove is caught, retried
        on CPU, and the served proof is digest-identical to a clean CPU
        prove."""
        from spectre_tpu.prover_service import selfverify as SV
        st = _ToyState(toy)
        faults.install_plan("proof.bytes:corrupt:1")
        v0 = HEALTH.get("proofs_verified")
        f0 = HEALTH.get("proofs_verify_failed")
        r0 = HEALTH.get("proofs_sdc_retried")
        sv0 = _self_verify_count()
        proof, inst = SV.verified_prove(st, "step", None)
        assert hashlib.sha256(proof).digest() \
            == hashlib.sha256(clean_cpu_proof).digest()
        assert proof == clean_cpu_proof
        assert inst == [st.out]
        # two proves: the corrupted one, then the pinned-to-CPU retry
        assert st.prove_backends == [None, "cpu"]
        assert HEALTH.get("proofs_verify_failed") == f0 + 1
        assert HEALTH.get("proofs_sdc_retried") == r0 + 1
        assert HEALTH.get("proofs_verified") == v0 + 1
        assert faults.armed("proof.bytes") == 0
        assert _self_verify_count() == sv0 + 2     # both attempts spanned

    def test_off_serves_fault_uncaught(self, toy, clean_cpu_proof,
                                       monkeypatch):
        """Negative pin: with the knob off the SAME fault reaches the
        caller unverified — proving the layer is load-bearing — and the
        self-verify span never opens."""
        from spectre_tpu.plonk.verifier import verify
        from spectre_tpu.prover_service import selfverify as SV
        monkeypatch.setenv(SV.ENV_VAR, "off")
        st = _ToyState(toy)
        faults.install_plan("proof.bytes:corrupt:1")
        sv0 = _self_verify_count()
        v0 = HEALTH.get("proofs_verified")
        proof, inst = SV.verified_prove(st, "step", None)
        assert proof != clean_cpu_proof                # corrupt bytes SERVED
        assert not verify(st.pk.vk, st.srs, [inst], proof)
        assert st.prove_backends == [None]             # no retry
        assert _self_verify_count() == sv0             # span skipped entirely
        assert HEALTH.get("proofs_verified") == v0

    def test_double_failure_raises_typed(self, toy):
        from spectre_tpu.prover_service import selfverify as SV
        st = _ToyState(toy)
        faults.install_plan("proof.bytes:corrupt:2")   # retry corrupted too
        f0 = HEALTH.get("proofs_verify_failed")
        r0 = HEALTH.get("proofs_sdc_retried")
        with pytest.raises(SV.ProofVerifyFailed, match="self-verification"):
            SV.verified_prove(st, "step", None)
        assert st.prove_backends == [None, "cpu"]
        assert HEALTH.get("proofs_verify_failed") == f0 + 2
        assert HEALTH.get("proofs_sdc_retried") == r0 + 1

    def test_suspect_bytes_quarantined(self, toy, tmp_path):
        """Failed-verify bytes land in results/quarantine/ (named by
        their own sha256) when the state is attached to a store."""
        from spectre_tpu.prover_service import selfverify as SV
        from spectre_tpu.utils.artifacts import ArtifactStore

        class _Jobs:
            store = ArtifactStore(str(tmp_path))

        st = _ToyState(toy, jobs=_Jobs())
        faults.install_plan("proof.bytes:corrupt:2")
        with pytest.raises(SV.ProofVerifyFailed):
            SV.verified_prove(st, "step", None)
        qdir = os.path.join(str(tmp_path), "results", "quarantine")
        names = os.listdir(qdir)
        assert names
        for name in names:            # quarantine names ARE content hashes
            data = open(os.path.join(qdir, name), "rb").read()
            assert name == hashlib.sha256(data).hexdigest() + ".bin"

    def test_sampled_mode_uses_injectable_rng(self, toy, monkeypatch):
        from spectre_tpu.prover_service import selfverify as SV
        monkeypatch.setenv(SV.ENV_VAR, "sampled:0.5")
        draws = iter([0.9, 0.1])       # first skips (0.9 >= p), second checks
        monkeypatch.setattr(SV, "RNG", lambda: next(draws))
        st = _ToyState(toy)
        sv0 = _self_verify_count()
        SV.verified_prove(st, "step", None)
        assert _self_verify_count() == sv0         # 0.9: skipped
        SV.verified_prove(st, "step", None)
        assert _self_verify_count() == sv0 + 1     # 0.1: verified

    def test_policy_parsing_fails_safe(self, monkeypatch):
        from spectre_tpu.prover_service import selfverify as SV
        cases = {"always": ("always", 1.0), "off": ("off", 0.0),
                 "sampled:0.25": ("sampled", 0.25),
                 "sampled:2.0": ("sampled", 1.0),     # clamped
                 "SAMPLED:0.5": ("sampled", 0.5),     # case-insensitive
                 "": ("always", 1.0),
                 "typo": ("always", 1.0),             # fail SAFE, not open
                 "sampled:abc": ("always", 1.0)}
        for raw, want in cases.items():
            monkeypatch.setenv(SV.ENV_VAR, raw)
            assert SV.policy() == want, raw
        monkeypatch.delenv(SV.ENV_VAR)
        assert SV.policy() == ("always", 1.0)

    def test_state_without_verify_proof_skips(self, monkeypatch):
        """Duck-typed fakes (no verify_proof) pass through unverified —
        the RPC plumbing tests keep their canned proofs."""
        from spectre_tpu.prover_service import selfverify as SV

        class _Fake:
            def prove_step(self, args):
                return b"\x01" * 64, [7]

        sv0 = _self_verify_count()
        proof, inst = SV.verified_prove(_Fake(), "step", None)
        assert proof == b"\x01" * 64 and inst == [7]
        assert _self_verify_count() == sv0

    def test_self_check_reruns_after_sdc_retry(self, toy):
        from spectre_tpu.prover_service import selfverify as SV
        st = _ToyState(toy)
        st.self_check = SV.SelfCheck(runner=lambda: True)
        faults.install_plan("proof.bytes:corrupt:1")
        SV.verified_prove(st, "step", None)
        # the box re-proves its readiness after suspected SDC
        assert st.self_check.snapshot() == {"ok": True, "runs": 1,
                                            "last_error": None}


# ---------------------------------------------------------------------------
# readiness self-check
# ---------------------------------------------------------------------------

class TestSelfCheck:
    def test_tiny_circuit_prove_verify_passes(self):
        from spectre_tpu.prover_service import selfverify as SV
        sc = SV.SelfCheck()
        assert sc.run() is True
        assert sc.snapshot() == {"ok": True, "runs": 1, "last_error": None}

    def test_failing_runner_counts_and_records(self):
        from spectre_tpu.prover_service import selfverify as SV
        from spectre_tpu.utils.health import ServiceHealth
        h = ServiceHealth()
        sc = SV.SelfCheck(runner=lambda: False, health=h)
        assert sc.run() is False
        snap = sc.snapshot()
        assert snap["ok"] is False and "failed verification" in snap["last_error"]
        assert h.get("self_check_failures") == 1

        def boom():
            raise RuntimeError("srs missing")

        sc2 = SV.SelfCheck(runner=boom, health=h)
        assert sc2.run() is False
        assert "RuntimeError" in sc2.snapshot()["last_error"]
        assert h.get("self_check_failures") == 2


# ---------------------------------------------------------------------------
# artifact scrubber
# ---------------------------------------------------------------------------

def _digest_runner(method, params):
    faults.check("backend.prove")
    blob = json.dumps([method, params], sort_keys=True).encode()
    return {"proof": "0x" + hashlib.sha256(blob).hexdigest()}


def _mk_queue(tmp_path, **kw):
    from spectre_tpu.prover_service.jobs import JobQueue
    kw.setdefault("concurrency", 1)
    kw.setdefault("scrub_interval", 0)     # periodic thread off: scrub_now
    return JobQueue(_digest_runner, journal_dir=str(tmp_path), **kw)


class TestScrubber:
    def test_corrupt_result_quarantined_live_survives(self, tmp_path):
        """Acceptance pin (scrubber half): a hand-corrupted result file
        is quarantined; the live job's intact artifacts are untouched."""
        q = _mk_queue(tmp_path, scrub_min_age=0)
        j1 = q.submit("m", {"w": 1})
        j2 = q.submit("m", {"w": 2})
        job1, job2 = q.wait(j1, timeout=10), q.wait(j2, timeout=10)
        assert job1.status == "done" and job2.status == "done"
        victim = q.store.path_for(job1.result_digest)
        with open(victim, "r+b") as f:
            f.seek(3)
            f.write(b"\xff")
        c0 = HEALTH.get("artifacts_scrub_corrupt")
        s0 = HEALTH.get("artifacts_scrubbed")
        summary = q.scrub_now()
        assert summary["corrupt"] == 1 and summary["expired"] == 0
        assert summary["scanned"] >= 4      # 2 results + 2 manifests
        assert HEALTH.get("artifacts_scrub_corrupt") == c0 + 1
        assert HEALTH.get("artifacts_scrubbed") == s0 + summary["scanned"]
        assert not os.path.exists(victim)
        assert os.path.exists(os.path.join(
            q.store.quarantine_dir, os.path.basename(victim)))
        # job2's artifacts are untouched and still served
        assert os.path.exists(q.store.path_for(job2.result_digest))
        assert q.result(j2).result == _digest_runner("m", {"w": 2})
        q.stop()

    def test_compact_then_scrub_expires_orphans(self, tmp_path, monkeypatch):
        """Acceptance pin (orphan half), closing the PR-8 follow-up: an
        artifact whose job the journal no longer knows (here: its lines
        hand-pruned, the compaction-retention scenario) is expired by the
        post-compaction scrub pass; live jobs' artifacts survive."""
        q = _mk_queue(tmp_path)
        ja = q.submit("m", {"w": 10})
        jb = q.submit("m", {"w": 11})
        a, b = q.wait(ja, timeout=10), q.wait(jb, timeout=10)
        assert a.result_digest and b.result_digest and b.manifest_digest
        q.stop()
        # drop job B from the journal entirely
        jpath = q.journal.path
        kept = [ln for ln in open(jpath).read().splitlines()
                if json.loads(ln).get("job_id") != jb]
        with open(jpath, "w") as f:
            f.write("\n".join(kept) + "\n")
        e0 = HEALTH.get("artifacts_expired")
        # force startup compaction, then the scrub pass that follows it
        monkeypatch.setenv("SPECTRE_JOURNAL_COMPACT_BYTES", "1")
        q2 = _mk_queue(tmp_path, scrub_min_age=0)
        assert HEALTH.get("artifacts_expired") == e0 + 2   # B's .bin+manifest
        assert not os.path.exists(q2.store.path_for(b.result_digest))
        assert not os.path.exists(q2.store.path_for(
            b.manifest_digest, ".manifest.json"))
        # A survived intact — replayed AND re-readable
        assert os.path.exists(q2.store.path_for(a.result_digest))
        assert q2.result(ja).result == _digest_runner("m", {"w": 10})
        assert q2.manifest(ja) is not None
        q2.stop()

    def test_min_age_guards_unjournaled_writes(self, tmp_path):
        """An orphan younger than scrub_min_age is NOT reaped — the race
        guard for artifacts written moments before their journal record."""
        q = _mk_queue(tmp_path, scrub_min_age=3600)
        orphan = q.store.write(b"freshly written, not yet journaled")
        summary = q.scrub_now()
        assert summary["expired"] == 0
        assert os.path.exists(q.store.path_for(orphan))
        # with the guard off the same file is an expirable orphan
        q.scrubber.min_age_s = 0
        assert q.scrub_now()["expired"] == 1
        assert not os.path.exists(q.store.path_for(orphan))
        q.stop()

    def test_periodic_thread_runs_with_injectable_interval(self, tmp_path):
        q = _mk_queue(tmp_path, scrub_interval=0.01, scrub_min_age=0)
        q.store.write(b"orphan for the periodic pass")
        deadline = time.time() + 5
        while time.time() < deadline:
            if not [n for n in os.listdir(q.store.dir)
                    if n.endswith(".bin")]:
                break
            time.sleep(0.01)
        else:
            pytest.fail("periodic scrubber never expired the orphan")
        q.stop()
        assert q.scrubber._thread is not None

    def test_scrub_skips_foreign_and_tmp_files(self, tmp_path):
        from spectre_tpu.prover_service.scrubber import parse_name
        assert parse_name("ab" * 32 + ".bin") == ("ab" * 32, ".bin")
        assert parse_name("ab" * 32 + ".manifest.json") \
            == ("ab" * 32, ".manifest.json")
        assert parse_name("ab" * 32 + ".bin.tmp") is None
        assert parse_name("ab" * 32) is None            # no suffix
        assert parse_name("notahash.bin") is None
        assert parse_name("ZZ" * 32 + ".bin") is None   # not lowercase hex
        q = _mk_queue(tmp_path, scrub_min_age=0)
        stranger = os.path.join(q.store.dir, "README.txt")
        with open(stranger, "w") as f:
            f.write("operator note")
        summary = q.scrub_now()
        assert summary["skipped"] >= 1
        assert os.path.exists(stranger)                 # never touched
        q.stop()

    def test_cli_scrub_offline(self, tmp_path, capsys):
        from spectre_tpu.prover_service.cli import main
        from spectre_tpu.utils.artifacts import ArtifactStore
        store = ArtifactStore(str(tmp_path))
        store.write(b"orphan: no journal references me")
        main(["scrub", "--params-dir", str(tmp_path)])
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert out["expired"] == 1 and out["corrupt"] == 0
        assert out["live"] == 0

    def test_compact_then_scrub_keeps_follower_chain_artifacts(
            self, tmp_path, monkeypatch):
        """ISSUE 10 satellite, extending the compact-then-scrub matrix:
        journal compaction + the scrub pass that follows must NEVER
        expire an artifact the follower's update chain references, even
        though no JOB journal record mentions it — the UpdateStore's
        live_artifacts keep-set rides the queue's live-provider hook. A
        genuine orphan in the same pass is still expired."""
        from spectre_tpu.follower.updates import UPDATE_SUFFIX, UpdateStore

        q = _mk_queue(tmp_path)
        jid = q.submit("m", {"w": 20})
        assert q.wait(jid, timeout=10).status == "done"
        store = UpdateStore(str(tmp_path))
        r1 = store.append_committee(1, {"proof": "0x02",
                                        "committee_poseidon": "0xaa"})
        r2 = store.append_committee(2, {"proof": "0x03",
                                        "committee_poseidon": "0xbb"})
        orphan = q.store.write(b"orphan: nothing references me")
        q.stop()

        e0 = HEALTH.get("artifacts_expired")
        # force startup compaction; the post-compaction scrub runs with
        # the follower keep-set registered (the `follow` CLI wiring)
        monkeypatch.setenv("SPECTRE_JOURNAL_COMPACT_BYTES", "1")
        q2 = _mk_queue(tmp_path, scrub_min_age=0,
                       live_providers=[store.live_artifacts])
        assert HEALTH.get("artifacts_expired") == e0 + 1   # the orphan only
        assert not os.path.exists(q2.store.path_for(orphan))
        for rec in (r1, r2):
            assert os.path.exists(
                q2.store.path_for(rec["digest"], UPDATE_SUFFIX))
        # the chain replays and serves from the surviving artifacts
        store2 = UpdateStore(str(tmp_path))
        assert store2.tip_period() == 2
        assert store2.verify_chain()
        assert store2.get_committee(1)["result"]["committee_poseidon"] \
            == "0xaa"
        # negative control: WITHOUT the provider the same artifacts are
        # orphans and the scrub reaps them
        q2.stop()
        q3 = _mk_queue(tmp_path, scrub_min_age=0)
        q3.scrub_now()
        assert not os.path.exists(
            q3.store.path_for(r1["digest"], UPDATE_SUFFIX))
        q3.stop()


class TestScrubberPacing:
    def test_overrun_stretches_interval_and_counts(self, tmp_path):
        """ISSUE 10 satellite: a pass that blew SPECTRE_SCRUB_BUDGET_S
        stretches the next wait by the overrun ratio (capped) and counts
        scrub_passes_deferred; a within-budget pass keeps the cadence."""
        from spectre_tpu.prover_service.scrubber import MAX_STRETCH, Scrubber
        from spectre_tpu.utils.artifacts import ArtifactStore

        ticks = iter([0.0, 120.0,      # pass 1: 120 s wall clock
                      200.0, 205.0,    # pass 2: 5 s
                      300.0, 300.0 + 30.0 * MAX_STRETCH * 4])  # pass 3: huge
        store = ArtifactStore(str(tmp_path))
        sc = Scrubber(store, lambda: set(), min_age_s=0, budget_s=30.0,
                      clock=lambda: next(ticks))
        d0 = HEALTH.get("scrub_passes_deferred")

        sc.scrub()
        assert sc.last_pass_s == 120.0
        assert sc.next_interval(300.0) == pytest.approx(300.0 * 4)  # 120/30
        assert HEALTH.get("scrub_passes_deferred") == d0 + 1

        sc.scrub()                     # fast pass: cadence restored
        assert sc.last_pass_s == 5.0
        assert sc.next_interval(300.0) == 300.0
        assert HEALTH.get("scrub_passes_deferred") == d0 + 1

        sc.scrub()                     # pathological pass: stretch capped
        assert sc.next_interval(300.0) == pytest.approx(300.0 * MAX_STRETCH)
        assert HEALTH.get("scrub_passes_deferred") == d0 + 2

    def test_budget_zero_disables_pacing(self, tmp_path):
        from spectre_tpu.prover_service.scrubber import Scrubber
        from spectre_tpu.utils.artifacts import ArtifactStore
        sc = Scrubber(ArtifactStore(str(tmp_path)), lambda: set(),
                      min_age_s=0, budget_s=0.0)
        sc.last_pass_s = 1e9
        assert sc.next_interval(300.0) == 300.0
