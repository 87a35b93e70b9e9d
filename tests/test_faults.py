"""Fault-injection tier (PR 3): every retry/degradation path in the
resilient prover service, exercised deterministically via
spectre_tpu.utils.faults (SPECTRE_FAULT_PLAN). Seconds-scale on tiny
specs/k — runs in the default tier and via `make test-faults`.

Covers the ISSUE-3 acceptance gates:
  * beacon client survives >=3 injected transient failures with backoff
    then succeeds; Retry-After honored; circuit breaker trips, fails
    fast, half-opens on cooldown and closes on success
  * a device-prove fault degrades to the CPU backend and the proof is
    byte-identical to a clean CPU prove (seeded blinding)
  * journal replay after a mid-prove crash re-runs the job and yields
    the same result digest as an uninterrupted run
  * fixed-base MSM degrades to glv+signed when one table would bust the
    byte budget — identical group element, no table build
"""

import hashlib
import json
import random
import threading
import time
from http.server import BaseHTTPRequestHandler, HTTPServer

import pytest

from spectre_tpu.utils import faults
from spectre_tpu.utils.health import HEALTH, ServiceHealth


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


class TestFaultPlan:
    def test_grammar(self):
        plan = faults.parse_plan("beacon.fetch:http503:3,backend.prove:oom")
        assert plan == [["beacon.fetch", "http503", 3],
                        ["backend.prove", "oom", 1]]
        assert faults.parse_plan("") == []

    def test_grammar_rejects_bad_entries(self):
        with pytest.raises(ValueError, match="unknown fault kind"):
            faults.parse_plan("site:frobnicate")
        with pytest.raises(ValueError, match="bad fault-plan entry"):
            faults.parse_plan("justasite")
        with pytest.raises(ValueError, match="bad fault count"):
            faults.parse_plan("s:raise:0")

    def test_fires_count_then_disarms(self):
        faults.install_plan("x.y:raise:2")
        for _ in range(2):
            with pytest.raises(faults.InjectedFault):
                faults.check("x.y")
        faults.check("x.y")            # exhausted: no-op
        faults.check("unrelated.site")  # never armed: no-op
        assert faults.fired_count("x.y") == 2
        assert faults.armed("x.y") == 0

    def test_env_plan(self, monkeypatch):
        faults.clear()
        monkeypatch.setenv(faults.ENV_VAR, "env.site:timeout:1")
        with pytest.raises(TimeoutError):
            faults.check("env.site")
        faults.check("env.site")       # count exhausted
        monkeypatch.delenv(faults.ENV_VAR)

    def test_kind_exceptions(self):
        import urllib.error
        faults.install_plan(
            "a:http503,a:http429,a:connreset,a:ioerror,a:compile")
        with pytest.raises(urllib.error.HTTPError) as e:
            faults.check("a")
        assert e.value.code == 503
        with pytest.raises(urllib.error.HTTPError) as e:
            faults.check("a")
        assert e.value.code == 429
        with pytest.raises(ConnectionResetError):
            faults.check("a")
        with pytest.raises(OSError):
            faults.check("a")
        with pytest.raises(faults.InjectedFault) as e:
            faults.check("a")
        assert e.value.kind == "compile"


# ---------------------------------------------------------------------------
# beacon client resilience
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def beacon_server():
    root = "0x" + (b"\xab" * 32).hex()

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path != "/eth/v1/beacon/blocks/head/root":
                self.send_response(404)
                self.end_headers()
                return
            body = json.dumps({"data": {"root": root}}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    httpd = HTTPServer(("127.0.0.1", 0), Handler)
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    yield f"http://127.0.0.1:{httpd.server_port}", root
    httpd.shutdown()


def _client(url, **kw):
    from spectre_tpu.preprocessor.beacon import BeaconClient
    kw.setdefault("timeout", 5.0)
    kw.setdefault("retries", 5)
    kw.setdefault("backoff_base", 0.001)
    kw.setdefault("backoff_max", 0.01)
    kw.setdefault("total_timeout", 30.0)
    kw.setdefault("breaker_threshold", 100)
    kw.setdefault("breaker_cooldown", 0.05)
    return BeaconClient(url, **kw)


class TestBeaconResilience:
    def test_survives_transient_failures_with_backoff(self, beacon_server):
        url, root = beacon_server
        sleeps = []
        c = _client(url, sleep=sleeps.append)
        faults.install_plan("beacon.fetch:http503:3")
        r0 = HEALTH.get("beacon_retries")
        assert c.head_block_root() == root
        assert faults.fired_count("beacon.fetch") == 3
        assert len(sleeps) == 3                 # one backoff per failure
        assert HEALTH.get("beacon_retries") == r0 + 3
        assert c.breaker_state == "closed"

    def test_backoff_grows_exponentially(self, beacon_server):
        url, _ = beacon_server
        sleeps = []
        # rng pinned to 1.0: delay == min(max, base * 2^i) exactly
        c = _client(url, sleep=sleeps.append, rng=lambda: 1.0,
                    backoff_base=0.001, backoff_max=1.0)
        faults.install_plan("beacon.fetch:timeout:4")
        c.head_block_root()
        assert sleeps == [0.001, 0.002, 0.004, 0.008]

    def test_retry_after_honored(self, beacon_server):
        url, _ = beacon_server
        sleeps = []
        # rng 0.0 would give zero backoff; Retry-After (0.01 on the
        # injected 429) must floor the delay
        c = _client(url, sleep=sleeps.append, rng=lambda: 0.0)
        faults.install_plan("beacon.fetch:http429:1")
        c.head_block_root()
        assert sleeps == [0.01]

    def test_non_transient_raises_immediately(self, beacon_server):
        import urllib.error
        url, _ = beacon_server
        sleeps = []
        c = _client(url, sleep=sleeps.append)
        with pytest.raises(urllib.error.HTTPError):
            c._get("/nonexistent")
        assert sleeps == []

    def test_total_deadline_exceeded(self, beacon_server):
        url, _ = beacon_server
        c = _client(url, total_timeout=0.0)
        with pytest.raises(TimeoutError, match="total deadline"):
            c.head_block_root()

    def test_breaker_trips_fails_fast_half_opens(self, beacon_server,
                                                 monkeypatch):
        from spectre_tpu.preprocessor.beacon import CircuitBreakerOpen
        url, root = beacon_server
        c = _client(url, breaker_threshold=3, breaker_cooldown=0.05)
        # the breaker's clock is the test's: "open" holds until the test
        # moves it, however long the host took between two statements
        now = [1000.0]
        monkeypatch.setattr(c._breaker, "_clock", lambda: now[0])
        trips0 = HEALTH.get("beacon_breaker_trips")
        faults.install_plan("beacon.fetch:connreset:10")
        # 3 consecutive failures trip the breaker mid-call
        with pytest.raises(CircuitBreakerOpen):
            c.head_block_root()
        assert faults.fired_count("beacon.fetch") == 3
        assert HEALTH.get("beacon_breaker_trips") == trips0 + 1
        # open: fail fast, no network attempt
        with pytest.raises(CircuitBreakerOpen):
            c.head_block_root()
        assert faults.fired_count("beacon.fetch") == 3
        # cooldown elapses -> half-open admits a trial; it fails (faults
        # still armed) and the breaker re-opens (counted as a trip)
        now[0] += 0.06
        assert c.breaker_state == "half-open"
        with pytest.raises(CircuitBreakerOpen):
            c.head_block_root()
        assert faults.fired_count("beacon.fetch") == 4
        assert HEALTH.get("beacon_breaker_trips") == trips0 + 2
        assert HEALTH.get("beacon_breaker_half_open") >= 1
        # cooldown again; disarm faults -> the half-open trial succeeds
        # and the breaker closes
        faults.clear()
        now[0] += 0.06
        assert c.head_block_root() == root
        assert c.breaker_state == "closed"


# ---------------------------------------------------------------------------
# device-prove -> CPU degradation (byte-identical proof)
# ---------------------------------------------------------------------------

K = 6


def _toy_proof_setup():
    from spectre_tpu.plonk import backend as B
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
    """The reference proof: a clean CPU prove with seeded blinding (every
    fallback prove below must reproduce these exact bytes)."""
    from spectre_tpu.plonk import backend as B
    from spectre_tpu.plonk.prover import prove
    pk, srs, asg, _ = toy
    return prove(pk, srs, asg, B.get_backend("cpu"),
                 blinding_rng=_seeded_rng())


class _FakeDeviceBackend:
    """Stands in for TpuBackend at the classification layer (the injected
    fault fires before any backend op runs, so no real device is needed)."""
    name = "tpu"


class TestBackendCpuFallback:
    def test_oom_degrades_byte_identical(self, toy, clean_cpu_proof):
        from spectre_tpu.plonk import backend as B
        from spectre_tpu.plonk.prover import prove
        from spectre_tpu.plonk.verifier import verify
        pk, srs, asg, out = toy
        faults.install_plan("backend.prove:oom:1")
        f0 = HEALTH.get("prove_cpu_fallbacks_oom")
        got = B.prove_with_fallback(
            lambda bk: prove(pk, srs, asg, bk, blinding_rng=_seeded_rng()),
            _FakeDeviceBackend())
        assert got == clean_cpu_proof          # byte-identical to clean CPU
        assert verify(pk.vk, srs, [[out]], got)
        assert HEALTH.get("prove_cpu_fallbacks_oom") == f0 + 1
        assert faults.armed("backend.prove") == 0

    def test_compile_failure_degrades(self, toy, clean_cpu_proof):
        from spectre_tpu.plonk import backend as B
        from spectre_tpu.plonk.prover import prove
        pk, srs, asg, _ = toy
        faults.install_plan("backend.prove:compile:1")
        f0 = HEALTH.get("prove_cpu_fallbacks_compile")
        got = B.prove_with_fallback(
            lambda bk: prove(pk, srs, asg, bk, blinding_rng=_seeded_rng()),
            _FakeDeviceBackend())
        assert got == clean_cpu_proof
        assert HEALTH.get("prove_cpu_fallbacks_compile") == f0 + 1

    def test_already_on_cpu_no_retry_loop(self):
        from spectre_tpu.plonk import backend as B
        faults.install_plan("backend.prove:oom:1")
        with pytest.raises(faults.InjectedFault):
            B.prove_with_fallback(lambda bk: b"unreached",
                                  B.get_backend("cpu"))

    def test_non_degradable_errors_propagate(self):
        from spectre_tpu.plonk import backend as B

        def bad_witness(bk):
            raise AssertionError("witness violates gate")

        with pytest.raises(AssertionError, match="witness violates"):
            B.prove_with_fallback(bad_witness, _FakeDeviceBackend())

    def test_classifiers(self):
        from spectre_tpu.plonk import backend as B
        assert B.is_device_oom(faults.InjectedFault("s", "oom"))
        assert not B.is_device_oom(faults.InjectedFault("s", "compile"))
        assert B.is_compile_failure(faults.InjectedFault("s", "compile"))
        assert not B.is_compile_failure(ValueError("nope"))
        assert not B.is_device_oom(MemoryError("host, not device"))


# ---------------------------------------------------------------------------
# job queue: journal recovery, dedup, timeout, cancellation
# ---------------------------------------------------------------------------

def _digest_runner(method, params):
    """Deterministic stand-in prover: result is a pure function of the
    witness, with the backend.prove fault site threaded through like the
    real runner."""
    faults.check("backend.prove")
    blob = json.dumps([method, params], sort_keys=True).encode()
    return {"proof": "0x" + hashlib.sha256(blob).hexdigest()}


class TestJobQueue:
    def _mk(self, tmp_path, runner=_digest_runner, **kw):
        from spectre_tpu.prover_service.jobs import JobQueue
        kw.setdefault("concurrency", 1)
        return JobQueue(runner, journal_dir=str(tmp_path), **kw)

    def test_submit_poll_result(self, tmp_path):
        q = self._mk(tmp_path)
        jid = q.submit("m", {"w": 1})
        job = q.wait(jid, timeout=10)
        assert job.status == "done"
        assert job.result == _digest_runner("m", {"w": 1})
        assert q.status(jid)["status"] == "done"
        q.stop()

    def test_dedup_by_witness_digest(self, tmp_path):
        q = self._mk(tmp_path)
        d0 = HEALTH.get("jobs_deduped")
        j1 = q.submit("m", {"w": 2})
        j2 = q.submit("m", {"w": 2})     # identical witness: same job
        j3 = q.submit("m", {"w": 3})
        assert j1 == j2 and j1 != j3
        assert HEALTH.get("jobs_deduped") == d0 + 1
        q.wait(j1, timeout=10)
        # done jobs stay dedup'd (a retried client gets the cached result)
        assert q.submit("m", {"w": 2}) == j1
        q.stop()

    def test_timeout_marks_failed(self, tmp_path):
        def slow(method, params):
            time.sleep(0.5)
            return {"ok": True}

        q = self._mk(tmp_path, runner=slow)
        jid = q.submit("m", {"w": 4}, timeout=0.05)
        job = q.wait(jid, timeout=10)
        assert job.status == "failed"
        assert job.error["kind"] == "TimeoutError"
        q.stop()

    def test_cancel_queued_job(self, tmp_path):
        release = threading.Event()

        def blocking(method, params):
            release.wait(5)
            return {"ok": True}

        q = self._mk(tmp_path, runner=blocking, concurrency=1)
        j1 = q.submit("m", {"w": 5})
        j2 = q.submit("m", {"w": 6})    # stuck behind j1
        assert q.cancel(j2)
        release.set()
        assert q.wait(j2, timeout=10).status == "cancelled"
        assert q.wait(j1, timeout=10).status == "done"
        q.stop()

    def test_journal_write_fault_fails_job_not_queue(self, tmp_path):
        q = self._mk(tmp_path)
        faults.install_plan("journal.write:ioerror:1")
        jid = q.submit("m", {"w": 7})
        job = q.wait(jid, timeout=10)
        assert job.status == "failed"
        assert job.error["kind"] == "OSError"
        # the queue survives: the next submit proves normally
        j2 = q.submit("m", {"w": 8})
        assert q.wait(j2, timeout=10).status == "done"
        q.stop()

    def test_torn_journal_tail_tolerated(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobJournal
        q = self._mk(tmp_path)
        jid = q.submit("m", {"w": 9})
        q.wait(jid, timeout=10)
        q.stop()
        # simulate a crash mid-append: torn, non-JSON final line
        with open(q.journal.path, "a") as f:
            f.write('{"event": "running", "job_')
        replayed = JobJournal(str(tmp_path)).replay()
        assert replayed[jid].status == "done"

    def test_crash_recovery_same_digest(self, tmp_path):
        """ISSUE-3 acceptance: kill a worker mid-prove (injected crash),
        restart the queue over the same params_dir, and the journal replay
        re-runs the job to the same result digest as an uninterrupted
        run."""
        import threading as _t
        q = self._mk(tmp_path)
        faults.install_plan("backend.prove:crash:1")
        r0 = HEALTH.get("jobs_requeued")
        # the InjectedCrash kills the worker thread like a dead process;
        # silence the default excepthook traceback spam
        old_hook = _t.excepthook
        _t.excepthook = lambda args: None
        try:
            jid = q.submit("m", {"w": 10})
            deadline = time.time() + 10
            while time.time() < deadline:
                st = q.status(jid)
                if st["status"] == "running" and not any(
                        w.is_alive() for w in q._workers):
                    break
                time.sleep(0.01)
            else:
                pytest.fail("worker did not crash")
        finally:
            _t.excepthook = old_hook
        # in-memory state died mid-prove: the journal's last record for
        # the job is "running" with no terminal event
        # --- restart: a fresh queue over the same journal dir ---
        q2 = self._mk(tmp_path)
        assert HEALTH.get("jobs_requeued") == r0 + 1
        job = q2.wait(jid, timeout=10)
        assert job.status == "done"
        assert job.result == _digest_runner("m", {"w": 10})
        assert job.attempts >= 1
        q2.stop()

    def test_manifest_write_fault_never_fails_prove(self, tmp_path):
        """ISSUE-8 pin: the provenance-manifest sink is IO-tolerant by
        the metrics.write contract — a broken disk at `manifest.write`
        costs the manifest (counted), never the prove."""
        q = self._mk(tmp_path)
        m0 = HEALTH.get("manifest_write_failures")
        faults.install_plan("manifest.write:ioerror:1")
        jid = q.submit("m", {"w": 40})
        job = q.wait(jid, timeout=10)
        assert job.status == "done"
        assert job.result == _digest_runner("m", {"w": 40})
        assert job.manifest_digest is None
        assert q.manifest(jid) is None
        assert HEALTH.get("manifest_write_failures") == m0 + 1
        # the fault is spent: the next prove manifests normally
        j2 = q.submit("m", {"w": 41})
        job2 = q.wait(j2, timeout=10)
        assert job2.status == "done" and job2.manifest_digest is not None
        assert q.manifest(j2)["result_digest"] == job2.result_digest
        q.stop()

    def test_journal_lives_under_params_dir(self, tmp_path):
        """ensure_jobs default wiring: the journal lands in the state's
        params_dir, so a service restart over the same dir recovers."""
        from spectre_tpu.prover_service.jobs import JOURNAL_NAME, ensure_jobs

        class S:
            spec = None
            concurrency = 1
            params_dir = str(tmp_path)
            jobs = None

        q = ensure_jobs(S(), runner=_digest_runner)
        jid = q.submit("m", {"w": 20})
        assert q.wait(jid, timeout=10).status == "done"
        assert (tmp_path / JOURNAL_NAME).exists()
        q.stop()

    def test_recovery_keeps_done_results(self, tmp_path):
        q = self._mk(tmp_path)
        jid = q.submit("m", {"w": 11})
        want = q.wait(jid, timeout=10).result
        q.stop()
        q2 = self._mk(tmp_path)
        # the restarted service still dedups + serves the journaled result
        assert q2.submit("m", {"w": 11}) == jid
        assert q2.result(jid).result == want
        q2.stop()


class TestJournalCompaction:
    """ROADMAP PR-3 follow-up (ISSUE 4 satellite): past a size threshold the
    startup replay rewrites the JSONL keeping only the terminal-state tail
    per job — the journal stops growing without bound, and a crash
    mid-compact loses NOTHING (atomic sidecar + replace)."""

    def _mk(self, tmp_path, **kw):
        from spectre_tpu.prover_service.jobs import JobQueue
        kw.setdefault("concurrency", 1)
        return JobQueue(_digest_runner, journal_dir=str(tmp_path), **kw)

    def test_compaction_shrinks_and_preserves_state(self, tmp_path,
                                                    monkeypatch):
        from spectre_tpu.prover_service.jobs import JOURNAL_NAME
        q = self._mk(tmp_path)
        jids = [q.submit("m", {"w": i}) for i in range(8)]
        results = {j: q.wait(j, timeout=10).result for j in jids}
        q.stop()
        path = tmp_path / JOURNAL_NAME
        before = path.stat().st_size
        # force the threshold below the journal size -> startup compacts
        monkeypatch.setenv("SPECTRE_JOURNAL_COMPACT_BYTES", "1")
        c0 = HEALTH.get("journal_compactions")
        q2 = self._mk(tmp_path)
        assert HEALTH.get("journal_compactions") == c0 + 1
        after = path.stat().st_size
        # submit+done per job vs submit+running+done: strictly smaller
        assert after < before
        # every result still served, dedup still pins the digests
        for jid in jids:
            assert q2.result(jid).result == results[jid]
            assert q2.submit("m", {"w": jids.index(jid)}) == jid
        q2.stop()
        # a THIRD restart replays the compacted journal identically
        q3 = self._mk(tmp_path)
        for jid in jids:
            assert q3.result(jid).result == results[jid]
        q3.stop()

    def test_compaction_drops_intermediate_transitions(self, tmp_path,
                                                       monkeypatch):
        from spectre_tpu.prover_service.jobs import JOURNAL_NAME
        q = self._mk(tmp_path)
        jid = q.submit("m", {"w": 1})
        q.wait(jid, timeout=10)
        q.stop()
        monkeypatch.setenv("SPECTRE_JOURNAL_COMPACT_BYTES", "1")
        q2 = self._mk(tmp_path)
        q2.stop()
        events = [json.loads(line)["event"]
                  for line in (tmp_path / JOURNAL_NAME).read_text()
                  .splitlines() if line]
        assert events == ["submit", "done"]     # no "running" tail noise

    def test_crash_mid_compact_loses_nothing(self, tmp_path, monkeypatch):
        """The ISSUE-4 hammer: an injected crash between staging the
        compacted sidecar and the atomic replace behaves like power loss —
        the ORIGINAL journal survives intact and the next startup both
        recovers every job and completes the deferred compaction."""
        from spectre_tpu.prover_service.jobs import JOURNAL_NAME
        q = self._mk(tmp_path)
        jids = [q.submit("m", {"w": i}) for i in range(4)]
        results = {j: q.wait(j, timeout=10).result for j in jids}
        q.stop()
        path = tmp_path / JOURNAL_NAME
        original = path.read_text()
        monkeypatch.setenv("SPECTRE_JOURNAL_COMPACT_BYTES", "1")
        faults.install_plan("journal.compact:crash:1")
        with pytest.raises(faults.InjectedCrash):
            self._mk(tmp_path)
        # the journal is byte-identical to before the attempt
        assert path.read_text() == original
        faults.clear()
        # restart after the "power loss": full recovery + compaction
        q2 = self._mk(tmp_path)
        for jid in jids:
            assert q2.result(jid).result == results[jid]
        assert path.stat().st_size < len(original)
        q2.stop()


# ---------------------------------------------------------------------------
# SRS load fault site
# ---------------------------------------------------------------------------

class TestSrsFaultSite:
    def test_srs_load_fault_fires(self, tmp_path):
        from spectre_tpu.plonk.srs import SRS
        faults.install_plan("srs.load:ioerror:1")
        with pytest.raises(OSError):
            SRS.load_or_setup(4, str(tmp_path))
        # disarmed: the retried load succeeds
        srs = SRS.load_or_setup(4, str(tmp_path))
        assert srs.k == 4


# ---------------------------------------------------------------------------
# ISSUE 6: admission control + backpressure
# ---------------------------------------------------------------------------

class TestAdmissionControl:
    """Overload-safe submission: a full queue (or a breached host-memory
    watermark) sheds NEW work with a typed ServiceOverloaded carrying a
    retry_after_s hint priced off the observed mean prove latency."""

    def _gated_runner(self):
        gate = threading.Event()
        started = threading.Event()

        def runner(method, params):
            started.set()
            assert gate.wait(timeout=30), "test forgot to open the gate"
            return _digest_runner(method, params)
        return runner, gate, started

    def test_queue_full_sheds_then_recovers(self, tmp_path):
        from spectre_tpu.prover_service.jobs import (JobQueue,
                                                     ServiceOverloaded)
        runner, gate, started = self._gated_runner()
        q = JobQueue(runner, concurrency=1, journal_dir=str(tmp_path),
                     queue_depth=1)
        shed0 = HEALTH.get("jobs_shed_queue")
        a = q.submit("m", {"w": "a"})
        assert started.wait(timeout=10)      # worker picked A up: running
        for _ in range(100):                 # drain race: wait off "queued"
            if q.status(a)["status"] == "running":
                break
            time.sleep(0.02)
        b = q.submit("m", {"w": "b"})        # fills the 1-deep backlog
        with pytest.raises(ServiceOverloaded) as exc:
            q.submit("m", {"w": "c"})
        assert exc.value.retry_after_s >= 1.0
        assert HEALTH.get("jobs_shed_queue") == shed0 + 1
        # ...but a DEDUP of already-admitted work is never shed
        assert q.submit("m", {"w": "b"}) == b
        gate.set()                           # drain
        assert q.wait(a, timeout=10).status == "done"
        assert q.wait(b, timeout=10).status == "done"
        # the retried submission now admits and completes
        c = q.submit("m", {"w": "c"})
        assert q.wait(c, timeout=10).status == "done"
        assert q.result(c).result == _digest_runner("m", {"w": "c"})
        q.stop()

    def test_memory_watermark_sheds(self, tmp_path):
        from spectre_tpu.prover_service.jobs import (JobQueue,
                                                     ServiceOverloaded,
                                                     rss_mb)
        if rss_mb() is None:
            pytest.skip("no /proc/self/statm on this platform")
        assert rss_mb() > 1.0                # a live CPython is >1MB
        q = JobQueue(_digest_runner, concurrency=1,
                     journal_dir=str(tmp_path), mem_watermark_mb=1.0)
        shed0 = HEALTH.get("jobs_shed_memory")
        with pytest.raises(ServiceOverloaded, match="memory watermark"):
            q.submit("m", {"w": 1})
        assert HEALTH.get("jobs_shed_memory") == shed0 + 1
        q.stop()

    def test_watermark_zero_disables(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobQueue
        q = JobQueue(_digest_runner, concurrency=1,
                     journal_dir=str(tmp_path), mem_watermark_mb=0)
        jid = q.submit("m", {"w": 2})
        assert q.wait(jid, timeout=10).status == "done"
        q.stop()

    def test_retry_after_priced_by_observed_latency(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobQueue
        h = ServiceHealth()
        h.observe("prove_latency_s", 10.0)
        h.observe("prove_latency_s", 20.0)   # mean 15s
        q = JobQueue(_digest_runner, concurrency=1,
                     journal_dir=str(tmp_path), health=h)
        assert q.retry_after_s() == 15.0     # empty backlog: one mean prove
        q.stop()

    def test_env_defaults(self, tmp_path, monkeypatch):
        from spectre_tpu.prover_service import jobs as J
        monkeypatch.setenv(J.QUEUE_DEPTH_ENV, "3")
        monkeypatch.setenv(J.MEM_WATERMARK_ENV, "123.5")
        monkeypatch.setenv(J.WORKER_STALL_ENV, "7.5")
        q = J.JobQueue(_digest_runner, concurrency=1,
                       journal_dir=str(tmp_path))
        assert q.queue_depth == 3
        assert q.mem_watermark_mb == 123.5
        assert q.stall_timeout == 7.5
        assert q.stats()["queue_depth"] == 3
        q.stop()


# ---------------------------------------------------------------------------
# ISSUE 6: deadline propagation + worker supervision
# ---------------------------------------------------------------------------

class TestDeadlinePropagation:
    def test_deadline_clamps_timeout(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobQueue
        q = JobQueue(_digest_runner, concurrency=1,
                     journal_dir=str(tmp_path), default_timeout=100.0)
        # client deadline below the server default wins...
        a = q.submit("m", {"w": "d1"}, deadline_s=0.5)
        assert q.result(a).timeout == 0.5
        # ...a LOOSER client deadline never relaxes the server's cap
        b = q.submit("m", {"w": "d2"}, timeout=0.25, deadline_s=50.0)
        assert q.result(b).timeout == 0.25
        q.stop()

    def test_deadline_expires_running_job(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobQueue
        gate = threading.Event()

        def runner(method, params):
            gate.wait(timeout=30)
            return _digest_runner(method, params)

        q = JobQueue(runner, concurrency=1, journal_dir=str(tmp_path))
        t0 = HEALTH.get("jobs_timed_out")
        jid = q.submit("m", {"w": "slow"}, deadline_s=0.15)
        job = q.wait(jid, timeout=10)
        assert job.status == "failed"
        assert job.error["kind"] == "TimeoutError"
        assert HEALTH.get("jobs_timed_out") == t0 + 1
        gate.set()
        q.stop()


class _SupervisorClock:
    """The supervisor's injectable clock, moved by the test and never by
    the wall: `advance` moves it, `scanned` waits until the supervisor has
    read the new time once and looked at every slot with it."""

    def __init__(self):
        self.now = 0.0
        self._reads = 0
        self._cv = threading.Condition()

    def __call__(self):
        with self._cv:
            if threading.current_thread().name == "prover-job-supervisor":
                self._reads += 1
                self._cv.notify_all()
            return self.now

    def advance(self, dt: float):
        with self._cv:
            self.now += dt

    def scanned(self, timeout=30.0):
        """Two reads from now: the second one's scan began after this call,
        so it saw the clock as it stands."""
        with self._cv:
            want = self._reads + 2
            assert self._cv.wait_for(lambda: self._reads >= want, timeout), \
                "the supervisor stopped scanning"


class TestWorkerSupervision:
    """A hung worker (wedged device call: heartbeat stops) is detected by
    the supervisor, its job failed(stalled), and a replacement thread takes
    the slot — other jobs keep completing. Time is the injected clock's:
    nothing here is decided by how long a sleep took on a loaded host."""

    def test_stalled_worker_replaced(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobQueue
        clock = _SupervisorClock()
        release = threading.Event()
        hung_thread = []

        def runner(method, params):
            if params.get("hang"):
                hung_thread.append(threading.current_thread())
                clock.advance(1.0)           # no heartbeat: presumed hung
                release.wait(timeout=30)
                return {"proof": "late"}
            return _digest_runner(method, params)

        q = JobQueue(runner, concurrency=1, journal_dir=str(tmp_path),
                     stall_timeout=0.3, sleep_interval=0.01, clock=clock)
        r0 = HEALTH.get("workers_replaced")
        hung = q.submit("m", {"hang": True})
        job = q.wait(hung, timeout=30)
        assert job.status == "failed"
        assert job.error["kind"] == "StalledWorker"
        assert HEALTH.get("workers_replaced") == r0 + 1
        # the REPLACEMENT worker serves new jobs
        ok = q.submit("m", {"w": "after-stall"})
        assert q.wait(ok, timeout=30).status == "done"
        # the disowned thread waking up must NOT resurrect the failed job
        release.set()
        hung_thread[0].join(timeout=30)
        assert not hung_thread[0].is_alive()
        assert q.result(hung).status == "failed"
        assert q.result(ok).result == _digest_runner("m",
                                                     {"w": "after-stall"})
        q.stop()

    def test_heartbeat_keeps_slow_prove_alive(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobQueue
        clock = _SupervisorClock()

        def runner(method, params, heartbeat=None):
            # a LEGITIMATE slow prove: 1.2 s of the clock in all, four
            # times stall_timeout, but never 0.3 s without a heartbeat,
            # and the supervisor looks at every step
            for _ in range(6):
                clock.advance(0.2)
                clock.scanned()
                heartbeat()
            return _digest_runner(method, params)

        q = JobQueue(runner, concurrency=1, journal_dir=str(tmp_path),
                     stall_timeout=0.3, sleep_interval=0.01, clock=clock)
        r0 = HEALTH.get("workers_replaced")
        jid = q.submit("m", {"w": "slow-but-alive"})
        assert q.wait(jid, timeout=30).status == "done"
        assert HEALTH.get("workers_replaced") == r0
        q.stop()


# ---------------------------------------------------------------------------
# ISSUE 6: integrity-checked artifact store
# ---------------------------------------------------------------------------

class TestArtifactStore:
    def _mk(self, tmp_path):
        from spectre_tpu.utils.artifacts import ArtifactStore
        return ArtifactStore(str(tmp_path))

    def test_write_read_roundtrip_and_dedup(self, tmp_path):
        import os
        store = self._mk(tmp_path)
        d = store.write(b"proof-bytes")
        assert store.read(d) == b"proof-bytes"
        assert os.path.exists(store.path_for(d))
        assert store.write(b"proof-bytes") == d     # content-addressed

    def test_bitflip_quarantined(self, tmp_path):
        import os
        from spectre_tpu.utils.artifacts import ArtifactCorrupt
        store = self._mk(tmp_path)
        d = store.write(b"proof-bytes")
        path = store.path_for(d)
        blob = bytearray(open(path, "rb").read())
        blob[3] ^= 0x40
        with open(path, "wb") as f:
            f.write(bytes(blob))
        q0 = HEALTH.get("artifacts_quarantined")
        with pytest.raises(ArtifactCorrupt):
            store.read(d)
        assert HEALTH.get("artifacts_quarantined") == q0 + 1
        assert not os.path.exists(path)             # moved, NOT deleted
        assert os.path.exists(os.path.join(str(store.quarantine_dir),
                                           os.path.basename(path)))
        # the slot is re-writable after quarantine (re-prove path)
        assert store.write(b"proof-bytes") == d
        assert store.read(d) == b"proof-bytes"

    def test_fault_corrupt_on_read(self, tmp_path):
        from spectre_tpu.utils.artifacts import ArtifactCorrupt
        store = self._mk(tmp_path)
        d = store.write(b"payload")
        faults.install_plan("artifact.read:corrupt:1")
        with pytest.raises(ArtifactCorrupt):
            store.read(d)
        assert faults.fired_count("artifact.read") == 1

    def test_fault_corrupt_on_write_detected_at_read(self, tmp_path):
        from spectre_tpu.utils.artifacts import ArtifactCorrupt
        store = self._mk(tmp_path)
        faults.install_plan("artifact.write:corrupt:1")
        d = store.write(b"payload")     # digest records the INTENDED bytes
        with pytest.raises(ArtifactCorrupt):
            store.read(d)

    def test_fault_ioerror_on_write(self, tmp_path):
        store = self._mk(tmp_path)
        faults.install_plan("artifact.write:ioerror:1")
        with pytest.raises(OSError):
            store.write(b"payload")
        assert store.write(b"payload")  # disarmed: succeeds


class TestResultOffload:
    """Job results live in the artifact store, the journal carries only
    their sha256 — the journal is O(#jobs) and a flipped result bit is
    caught (and quarantined) at replay instead of silently served."""

    def _mk(self, tmp_path, runner=_digest_runner, **kw):
        from spectre_tpu.prover_service.jobs import JobQueue
        kw.setdefault("concurrency", 1)
        return JobQueue(runner, journal_dir=str(tmp_path), **kw)

    def test_result_offloaded_and_identical_after_restart(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JOURNAL_NAME
        q = self._mk(tmp_path)
        jid = q.submit("m", {"w": "off"})
        job = q.wait(jid, timeout=10)
        want = _digest_runner("m", {"w": "off"})
        assert job.result == want
        assert job.result_digest is not None
        q.stop()
        # the payload is NOT inlined in the journal
        text = (tmp_path / JOURNAL_NAME).read_text()
        assert want["proof"] not in text
        assert job.result_digest in text
        q2 = self._mk(tmp_path)
        assert q2.result(jid).result == want        # re-verified hydrate
        q2.stop()

    def test_corrupt_result_quarantined_on_replay_then_reprovable(
            self, tmp_path):
        import os
        q = self._mk(tmp_path)
        jid = q.submit("m", {"w": "bits"})
        job = q.wait(jid, timeout=10)
        digest = job.result_digest
        q.stop()
        path = q.store.path_for(digest)
        blob = bytearray(open(path, "rb").read())
        blob[len(blob) // 2] ^= 0x01
        with open(path, "wb") as f:
            f.write(bytes(blob))
        q0 = HEALTH.get("artifacts_quarantined")
        q2 = self._mk(tmp_path)
        replayed = q2.result(jid)
        assert replayed.status == "failed"          # degraded, loudly
        assert replayed.error["kind"] == "ArtifactCorrupt"
        assert HEALTH.get("artifacts_quarantined") == q0 + 1
        assert not os.path.exists(path)
        # failed jobs do not pin the witness digest: resubmit RE-PROVES
        jid2 = q2.submit("m", {"w": "bits"})
        assert jid2 != jid
        assert q2.wait(jid2, timeout=10).result == _digest_runner(
            "m", {"w": "bits"})
        q2.stop()

    def test_journal_size_independent_of_payload(self, tmp_path,
                                                 monkeypatch):
        from spectre_tpu.prover_service.jobs import JOURNAL_NAME
        big = "ab" * 65536                           # 128KB proof payload

        def big_runner(method, params):
            return {"proof": big, "w": params["w"]}

        q = self._mk(tmp_path, runner=big_runner)
        jids = [q.submit("m", {"w": i}) for i in range(4)]
        for j in jids:
            assert q.wait(j, timeout=10).status == "done"
        q.stop()
        monkeypatch.setenv("SPECTRE_JOURNAL_COMPACT_BYTES", "1")
        q2 = self._mk(tmp_path, runner=big_runner)
        size = (tmp_path / JOURNAL_NAME).stat().st_size
        # O(#jobs): the compacted journal is smaller than ONE payload
        assert size < len(big)
        for i, j in enumerate(jids):
            assert q2.result(j).result == {"proof": big, "w": i}
        q2.stop()


class TestSrsChecksum:
    def test_sidecar_written_and_verified(self, tmp_path):
        from spectre_tpu.plonk.srs import SRS
        from spectre_tpu.utils.artifacts import SIDECAR_SUFFIX
        srs = SRS.load_or_setup(4, str(tmp_path))
        path = tmp_path / "kzg_bn254_4.srs"
        assert (tmp_path / ("kzg_bn254_4.srs" + SIDECAR_SUFFIX)).exists()
        assert SRS.read(str(path)).k == srs.k

    def test_bitflipped_srs_refused(self, tmp_path):
        from spectre_tpu.plonk.srs import SRS
        from spectre_tpu.utils.artifacts import ArtifactCorrupt
        SRS.load_or_setup(4, str(tmp_path))
        path = tmp_path / "kzg_bn254_4.srs"
        blob = bytearray(path.read_bytes())
        blob[40] ^= 0x08                             # one flipped tau limb
        path.write_bytes(bytes(blob))
        with pytest.raises(ArtifactCorrupt):
            SRS.read(str(path))
        with pytest.raises(ArtifactCorrupt):
            SRS.load_or_setup(4, str(tmp_path))      # load path refuses too

    def test_missing_sidecar_stays_loadable(self, tmp_path):
        from spectre_tpu.plonk.srs import SRS
        from spectre_tpu.utils.artifacts import SIDECAR_SUFFIX
        SRS.load_or_setup(4, str(tmp_path))
        (tmp_path / ("kzg_bn254_4.srs" + SIDECAR_SUFFIX)).unlink()
        assert SRS.read(str(tmp_path / "kzg_bn254_4.srs")).k == 4


class TestMetricsSinkFault:
    """ISSUE 7 satellite: the SPECTRE_METRICS JSONL sink is best-effort —
    a broken sink (full disk, revoked fd) must NEVER fail the prove it is
    observing; it counts on health and the next phase writes through."""

    def test_broken_sink_never_fails_a_prove(self, tmp_path, monkeypatch):
        from spectre_tpu.utils import profiling as prof
        sink = tmp_path / "metrics.jsonl"
        monkeypatch.setenv("SPECTRE_METRICS", str(sink))
        faults.install_plan("metrics.write:ioerror:1")
        before = HEALTH.get("metrics_write_failures")
        with prof.phase("sink-test-phase"):          # must not raise
            pass
        assert faults.fired_count("metrics.write") == 1
        assert HEALTH.get("metrics_write_failures") == before + 1
        assert not sink.exists()                     # faulted append skipped
        with prof.phase("sink-test-phase"):          # disarmed: writes thru
            pass
        lines = [json.loads(l) for l in sink.read_text().splitlines()]
        assert len(lines) == 1
        assert lines[0]["phase"] == "sink-test-phase"
        assert lines[0]["seconds"] >= 0


class TestDiskFullFault:
    """ISSUE 9 satellite: a full disk (`diskfull` kind = OSError ENOSPC)
    at any write site fails the JOB (or just the manifest, per the
    best-effort contract) with a typed error and a counter — never
    crashes the worker or wedges the queue."""

    def _mk(self, tmp_path, **kw):
        from spectre_tpu.prover_service.jobs import JobQueue
        kw.setdefault("concurrency", 1)
        return JobQueue(_digest_runner, journal_dir=str(tmp_path), **kw)

    def test_kind_raises_enospc(self):
        import errno
        faults.install_plan("d.site:diskfull:1")
        with pytest.raises(OSError) as e:
            faults.check("d.site")
        assert e.value.errno == errno.ENOSPC
        faults.check("d.site")         # spent: no-op

    def test_artifact_write_diskfull_fails_job_not_queue(self, tmp_path):
        q = self._mk(tmp_path)
        faults.install_plan("artifact.write:diskfull:1")
        jid = q.submit("m", {"w": 90})
        job = q.wait(jid, timeout=10)
        assert job.status == "failed"
        assert job.error["kind"] == "OSError"
        assert "ENOSPC" in job.error["message"]
        # queue survives: the next submit proves + persists normally
        j2 = q.submit("m", {"w": 91})
        job2 = q.wait(j2, timeout=10)
        assert job2.status == "done" and job2.result_digest is not None
        q.stop()

    def test_journal_write_diskfull_fails_job_not_queue(self, tmp_path):
        q = self._mk(tmp_path)
        faults.install_plan("journal.write:diskfull:1")
        jid = q.submit("m", {"w": 92})
        job = q.wait(jid, timeout=10)
        assert job.status == "failed"
        assert job.error["kind"] == "OSError"
        j2 = q.submit("m", {"w": 93})
        assert q.wait(j2, timeout=10).status == "done"
        q.stop()

    def test_manifest_write_diskfull_best_effort(self, tmp_path):
        # manifests are optional by contract: ENOSPC costs the manifest
        # (counted on manifest_write_failures), never the prove
        q = self._mk(tmp_path)
        m0 = HEALTH.get("manifest_write_failures")
        faults.install_plan("manifest.write:diskfull:1")
        jid = q.submit("m", {"w": 94})
        job = q.wait(jid, timeout=10)
        assert job.status == "done"
        assert job.manifest_digest is None and q.manifest(jid) is None
        assert HEALTH.get("manifest_write_failures") == m0 + 1
        q.stop()


class TestFaultSiteDocs:
    """The README fault-site table is generated, not hand-maintained.

    `python -m spectre_tpu.prover_service faults --list` prints
    `faults.render_site_table()`; the README embeds that output between
    `<!-- fault-sites:begin -->` / `<!-- fault-sites:end -->` markers.
    These pins make drift (a new site without a doc row, or a stale
    hand-edit) a test failure instead of a silent lie.
    """

    def _readme_block(self):
        import pathlib

        readme = pathlib.Path(__file__).resolve().parent.parent / "README.md"
        text = readme.read_text(encoding="utf-8")
        begin = "<!-- fault-sites:begin -->"
        end = "<!-- fault-sites:end -->"
        assert begin in text and end in text, "README fault-site markers missing"
        return text.split(begin, 1)[1].split(end, 1)[0].strip()

    def test_readme_table_matches_registry(self):
        assert self._readme_block() == faults.render_site_table().strip()

    def test_every_site_has_a_table_row(self):
        block = self._readme_block()
        for site in faults.SITES:
            assert f"`{site}`" in block

    def test_cli_faults_list_prints_table(self, capsys):
        from spectre_tpu.prover_service.cli import main

        assert main(["faults", "--list"]) in (0, None)
        out = capsys.readouterr().out
        assert faults.render_site_table().strip() in out

    def test_cli_faults_json_covers_sites_and_kinds(self, capsys):
        from spectre_tpu.prover_service.cli import main

        assert main(["faults", "--json"]) in (0, None)
        payload = json.loads(capsys.readouterr().out)
        assert set(payload["sites"]) == set(faults.SITES)
        assert tuple(payload["kinds"]) == faults.KINDS
