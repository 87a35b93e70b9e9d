"""Provenance-manifest tier (ISSUE 8): the proof flight recorder.

Pins the acceptance gates:
  * end to end: submit -> prove -> `getProofManifest` returns a
    manifest whose result digest matches `getProofResult`'s artifact,
    whose phase seconds agree with the `getTrace` span tree, and which
    survives a journal replay (digest-verified through the artifact
    store);
  * a second identical prove (same shapes, fresh params so dedup does
    not short-circuit) records ZERO new compile events — the jit-cache
    warmth signal;
  * queue-wait decomposition: the SAME float lands in the job record,
    the manifest and the `spectre_queue_wait_seconds` histogram;
  * RPC contract: -32004 unknown job, -32002 while live, -32006 when
    the manifest is absent/corrupt (the RESULT still serves);
  * the report CLI renders and diffs manifests from files and job ids.
"""

import json
import threading
import time
import urllib.request

import pytest

from spectre_tpu.observability import compilelog, manifest
from spectre_tpu.observability import metrics as M
from spectre_tpu.observability import tracing
from spectre_tpu.utils import faults
from spectre_tpu.utils.health import HEALTH
from spectre_tpu.utils import profiling as prof


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()


# ---------------------------------------------------------------------------
# unit: event collector, LRU deltas, canonical encoding, render/diff
# ---------------------------------------------------------------------------


class TestCollector:
    def test_record_event_noop_without_collector(self):
        manifest.record_event("orphan", x=1)     # must not raise

    def test_collect_events_thread_local_and_nested(self):
        with manifest.collect_events() as outer:
            manifest.record_event("a")
            with manifest.collect_events() as inner:
                manifest.record_event("b", n=2)
            manifest.record_event("c")
        assert outer == [{"kind": "a"}, {"kind": "c"}]
        assert inner == [{"kind": "b", "n": 2}]

    def test_injected_faults_land_in_collecting_manifest(self):
        """The faults.add_observer hook: a fault that fires while a job
        collects becomes a manifest event (site + kind)."""
        faults.install_plan("widget.io:ioerror:1")
        with manifest.collect_events() as ev:
            with pytest.raises(OSError):
                faults.check("widget.io")
        assert {"kind": "fault", "site": "widget.io",
                "fault_kind": "ioerror"} in ev

    def test_mangle_faults_observed_too(self):
        faults.install_plan("blob.site:corrupt:1")
        with manifest.collect_events() as ev:
            out = faults.mangle("blob.site", b"\x00" * 8)
        assert out != b"\x00" * 8
        assert ev == [{"kind": "fault", "site": "blob.site",
                       "fault_kind": "corrupt"}]


class TestLruDelta:
    def test_delta_counters_and_final_occupancy(self):
        before = {"msm": {"hits": 2, "builds": 1, "evictions": 0,
                          "recomputes": 0, "bytes": 10, "entries": 1},
                  "ntt": None}
        after = {"msm": {"hits": 5, "builds": 2, "evictions": 1,
                         "recomputes": 0, "bytes": 30, "entries": 2},
                 "ntt": None}
        d = manifest.lru_delta(before, after)
        assert d["msm"] == {"hits": 3, "builds": 1, "evictions": 1,
                            "recomputes": 0, "bytes": 30, "entries": 2}
        assert d["ntt"] is None

    def test_cache_loaded_mid_job_counts_from_zero(self):
        after = {"msm": {"hits": 1, "builds": 1, "evictions": 0,
                         "recomputes": 0, "bytes": 8, "entries": 1},
                 "ntt": None}
        d = manifest.lru_delta({"msm": None, "ntt": None}, after)
        assert d["msm"]["builds"] == 1


class TestEncoding:
    def _man(self):
        return manifest.build(
            job_id="job-1", method="m", witness_digest="ab" * 32,
            attempts=1, submitted=10.0, admitted=10.5, started=11.0,
            finished=14.0, queue_wait_s=0.5,
            events=[{"kind": "cpu_fallback", "fallback_kind": "oom"}],
            compile_events=[{"event": "backend_compile",
                             "fn": "prove/quotient", "seconds": 2.25}],
            peak_rss_mb=123.4, result_digest="cd" * 32)

    def test_round_trip_byte_stable(self):
        man = self._man()
        raw = manifest.to_bytes(man)
        again = manifest.from_bytes(raw)
        assert again == man
        assert manifest.to_bytes(again) == raw       # canonical: stable

    def test_prove_seconds_derived(self):
        man = self._man()
        assert man["prove_s"] == pytest.approx(3.0)
        assert man["compile"]["count"] == 1
        assert man["compile"]["by_fn"]["prove/quotient"]["seconds"] == 2.25

    def test_env_knobs_always_keyed(self):
        man = self._man()
        assert set(manifest.ENV_KNOBS) <= set(man["env"])

    def test_from_bytes_rejects_wrong_schema(self):
        with pytest.raises(ValueError, match="not a "):
            manifest.from_bytes(b'{"schema": "something/else"}')
        with pytest.raises(ValueError, match="not a "):
            manifest.from_bytes(b'[1, 2]')

    def test_render_mentions_the_load_bearing_facts(self):
        text = manifest.render(self._man())
        assert "job-1" in text
        assert "queue wait" in text and "0.500s" in text
        assert "prove" in text and "3.000s" in text
        assert "cpu_fallback" in text
        assert "prove/quotient" in text

    def test_diff_surfaces_regressions_and_knob_flips(self):
        a = self._man()
        b = json.loads(json.dumps(a))
        b["job_id"] = "job-2"
        b["prove_s"] = 9.0
        b["compile"] = {"count": 3, "seconds": 5.5, "by_fn": {},
                        "events": []}
        b["env"] = dict(a["env"], SPECTRE_MSM_MODE="glv")
        text = manifest.diff(a, b)
        assert "job-1 -> job-2" in text
        assert "+6.000s" in text                     # prove regression
        assert "compile count: 1 -> 3" in text
        assert "env.SPECTRE_MSM_MODE" in text


class TestCompilelog:
    def test_summarize_counts_backend_compile_only(self):
        events = [
            {"event": "jaxpr_trace", "fn": "p", "seconds": 0.1},
            {"event": "jaxpr_to_mlir_module", "fn": "p", "seconds": 0.2},
            {"event": "backend_compile", "fn": "p", "seconds": 1.5},
            {"event": "backend_compile", "fn": "q", "seconds": 0.5},
        ]
        s = compilelog.summarize(events)
        assert s["count"] == 2                       # not 4
        assert s["seconds"] == pytest.approx(2.0)
        assert s["by_fn"] == {"p": {"count": 1, "seconds": 1.5},
                              "q": {"count": 1, "seconds": 0.5}}
        assert len(s["events"]) == 4                 # sub-steps retained

    def test_listener_attributes_to_innermost_span(self):
        """Drive the listener directly (no jax needed): the event must
        hit the capture sink, the trace tree AND the fn-labelled
        histogram with the SAME rounded value."""
        M.COMPILE_SECONDS.reset()
        with tracing.trace("t-compile") as tr:
            with prof.phase("prove/commit_advice"):
                with compilelog.capture() as cev:
                    compilelog._listener(
                        "/jax/core/compile/backend_compile_duration",
                        0.123456789)
        assert cev == [{"event": "backend_compile",
                        "fn": "prove/commit_advice",
                        "seconds": 0.123457}]
        kids = M.COMPILE_SECONDS.children()
        assert [k.labels for k in kids] == [{"fn": "prove/commit_advice"}]
        assert kids[0].snapshot()["sum"] == 0.123457  # exact: same float
        names = [e["name"] for e in
                 tracing.chrome_trace(tr)["traceEvents"]]
        assert "compile/backend_compile" in names

    def test_listener_ignores_foreign_events(self):
        with compilelog.capture() as cev:
            compilelog._listener("/jax/core/something_else", 1.0)
        assert cev == []

    def test_persistent_cache_events_counted(self):
        """The plain-event listener (ISSUE 13): persistent compile-cache
        hit/miss events land in the capture sink and in summarize()'s
        persistent_cache key — the 'warm disk cache vs genuinely
        recompiled' signal."""
        before = compilelog.cache_counts()
        with compilelog.capture() as cev:
            compilelog._event_listener("/jax/compilation_cache/cache_hits")
            compilelog._event_listener("/jax/compilation_cache/cache_hits")
            compilelog._event_listener("/jax/compilation_cache/cache_misses")
            compilelog._event_listener("/jax/unrelated/event")
        assert [e["event"] for e in cev] == [
            "persistent_cache_hit", "persistent_cache_hit",
            "persistent_cache_miss"]
        s = compilelog.summarize(cev)
        assert s["persistent_cache"] == {"hit": 2, "miss": 1}
        assert s["count"] == 0            # cache events are not compiles
        after = compilelog.cache_counts()
        assert after["hit"] == before["hit"] + 2
        assert after["miss"] == before["miss"] + 1

    def test_unattributed_outside_any_span(self):
        with compilelog.capture() as cev:
            compilelog._listener(
                "/jax/core/compile/backend_compile_duration", 0.5)
        assert cev[0]["fn"] == compilelog.UNATTRIBUTED


# ---------------------------------------------------------------------------
# end to end through the JobQueue
# ---------------------------------------------------------------------------


def _runner(method, params):
    with prof.phase("prove/commit_advice"):
        time.sleep(0.002)
    with prof.phase("prove/quotient"):
        manifest.record_event("msm_fixed_degraded", n=64, window=4)
    return {"proof": "0xab", "w": params.get("w")}


def _mk(tmp_path, runner=_runner, **kw):
    from spectre_tpu.prover_service.jobs import JobQueue
    kw.setdefault("concurrency", 1)
    return JobQueue(runner, journal_dir=str(tmp_path), **kw)


class TestQueueManifest:
    def test_end_to_end_manifest_pin(self, tmp_path):
        """THE acceptance pin: digests match the result artifact, phase
        seconds agree with the getTrace span tree, queue wait has exact
        three-sink parity, and the manifest survives journal replay."""
        M.QUEUE_WAIT.reset()
        q = _mk(tmp_path)
        jid = q.submit("m", {"w": 1})
        job = q.wait(jid, timeout=10)
        assert job.status == "done"
        assert job.manifest_digest is not None

        man = q.manifest(jid)
        assert man is not None
        assert man["schema"] == manifest.SCHEMA
        assert man["job_id"] == jid
        assert man["witness_digest"] == job.digest
        # result digest matches the artifact getProofResult re-verifies
        assert man["result_digest"] == job.result_digest
        assert q.store.read(man["result_digest"]) is not None

        # phase seconds: same numbers the getTrace span tree yields
        tr = tracing.get_trace(jid)
        assert tr is not None
        assert man["phase_seconds"] == tracing.phase_seconds(tr)
        assert man["phase_seconds"]["prove/commit_advice"] >= 0.002

        # queue-wait: one float, three sinks, exact parity
        snap = M.QUEUE_WAIT.snapshot()
        assert snap["count"] == 1
        assert snap["sum"] == job.queue_wait_s == man["queue_wait_s"]
        ts = man["timestamps"]
        assert ts["submitted"] <= ts["admitted"] <= ts["started"] \
            <= ts["finished"]
        assert man["prove_s"] == pytest.approx(
            ts["finished"] - ts["started"], abs=1e-6)

        # the degrade event recorded inside the runner landed
        assert {"kind": "msm_fixed_degraded", "n": 64, "window": 4} \
            in man["events"]
        # the journal carries the digest, not the manifest body
        recs = [json.loads(ln) for ln in open(q.journal.path)]
        done = [r for r in recs if r.get("event") == "done"]
        assert done[0]["manifest_digest"] == job.manifest_digest
        assert all("phase_seconds" not in r for r in recs)
        q.stop()

        # replay: a fresh queue serves the byte-identical manifest
        q2 = _mk(tmp_path)
        j2 = q2.result(jid)
        assert j2.status == "done"
        assert j2.manifest_digest == job.manifest_digest
        assert j2.queue_wait_s is None       # not replayed: manifest has it
        assert q2.manifest(jid) == man
        q2.stop()

    def test_failed_jobs_get_manifests_too(self, tmp_path):
        def boom(method, params):
            with prof.phase("prove/commit_advice"):
                raise ValueError("witness is cursed")

        q = _mk(tmp_path, runner=boom)
        jid = q.submit("m", {"w": 2})
        job = q.wait(jid, timeout=10)
        assert job.status == "failed"
        man = q.manifest(jid)
        assert man is not None
        assert man["error"] == "ValueError: witness is cursed"
        assert man["result_digest"] is None
        assert "prove/commit_advice" in man["phase_seconds"]
        q.stop()

    def test_compact_preserves_manifest_digest_and_admitted(self, tmp_path):
        q = _mk(tmp_path)
        jid = q.submit("m", {"w": 3})
        job = q.wait(jid, timeout=10)
        man = q.manifest(jid)
        q.journal.compact(list(q._jobs.values()))
        q.stop()
        q2 = _mk(tmp_path)
        j2 = q2.result(jid)
        assert j2.manifest_digest == job.manifest_digest
        assert j2.admitted_at is not None
        assert q2.manifest(jid) == man
        q2.stop()

    def test_missing_manifest_artifact_still_serves_result(self, tmp_path):
        """A journaled job whose manifest artifact is GONE (disk cleanup,
        partial restore) still serves its result; the manifest degrades
        to absent with a counted read failure."""
        import os
        q = _mk(tmp_path)
        jid = q.submit("m", {"w": 4})
        job = q.wait(jid, timeout=10)
        assert job.status == "done"
        path = q.store.path_for(job.manifest_digest,
                                manifest.MANIFEST_SUFFIX)
        q.stop()
        os.remove(path)
        r0 = HEALTH.get("manifest_read_failures")
        q2 = _mk(tmp_path)
        res = q2.result(jid)
        assert res.status == "done" and res.result["proof"] == "0xab"
        assert q2.manifest(jid) is None
        assert HEALTH.get("manifest_read_failures") == r0 + 1
        q2.stop()

    def test_corrupt_manifest_artifact_quarantined_not_served(self, tmp_path):
        q = _mk(tmp_path)
        jid = q.submit("m", {"w": 5})
        job = q.wait(jid, timeout=10)
        path = q.store.path_for(job.manifest_digest,
                                manifest.MANIFEST_SUFFIX)
        with open(path, "r+b") as f:                 # flip one byte
            b = bytearray(f.read())
            b[len(b) // 2] ^= 0xFF
            f.seek(0)
            f.write(bytes(b))
        qn0 = HEALTH.get("artifacts_quarantined")
        assert q.manifest(jid) is None               # verification failed
        assert HEALTH.get("artifacts_quarantined") == qn0 + 1
        assert q.result(jid).status == "done"        # result unaffected
        q.stop()

    def test_crash_then_replay_manifest_from_rerun(self, tmp_path):
        """A worker killed mid-prove (InjectedCrash) writes NO manifest;
        the journal replay re-runs the job and the re-run writes one —
        the crash-recovery acceptance extended to provenance."""
        import threading as _t

        def runner(method, params):
            faults.check("backend.prove")
            return {"proof": "0xcd"}

        q = _mk(tmp_path, runner=runner)
        faults.install_plan("backend.prove:crash:1")
        old_hook = _t.excepthook
        _t.excepthook = lambda args: None
        try:
            jid = q.submit("m", {"w": 6})
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
        assert q.manifest(jid) is None               # crash wrote nothing
        q2 = _mk(tmp_path, runner=runner)
        job = q2.wait(jid, timeout=10)
        assert job.status == "done"
        man = q2.manifest(jid)
        assert man is not None
        assert man["result_digest"] == job.result_digest
        q2.stop()


# ---------------------------------------------------------------------------
# jit-cache warmth: a second identical prove compiles NOTHING
# ---------------------------------------------------------------------------

_JITTED = None


def _jit_fn():
    """One process-lifetime jitted callable: the second call with the
    same shape/dtype must be an XLA cache hit."""
    global _JITTED
    if _JITTED is None:
        import jax
        _JITTED = jax.jit(lambda a: a * a + 1.0)
    return _JITTED


def _jit_runner(method, params):
    import jax.numpy as jnp
    with prof.phase("prove/commit_advice"):
        val = _jit_fn()(jnp.float32(params["x"]))
    return {"proof": float(val)}


class TestCompileWarmth:
    def test_second_prove_records_zero_compiles(self, tmp_path):
        """Acceptance: two proves with DIFFERENT params (dedup must not
        short-circuit) but identical shapes — the first manifest records
        the backend compile, the second records zero compile events."""
        if not compilelog.install():
            pytest.skip("jax.monitoring unavailable in this process")
        q = _mk(tmp_path, runner=_jit_runner)
        j1 = q.submit("m", {"x": 1.5})
        assert q.wait(j1, timeout=60).status == "done"
        j2 = q.submit("m", {"x": 2.5})
        assert j2 != j1                              # fresh witness digest
        assert q.wait(j2, timeout=60).status == "done"
        m1, m2 = q.manifest(j1), q.manifest(j2)
        # the first prove MAY be warm too (another test already traced
        # this exact function); the second must ALWAYS be
        if m1["compile"]["count"]:
            assert m1["compile"]["by_fn"]["prove/commit_advice"]["count"] >= 1
        assert m2["compile"]["count"] == 0
        assert m2["compile"]["events"] == []
        q.stop()


# ---------------------------------------------------------------------------
# RPC + client + report CLI
# ---------------------------------------------------------------------------


def _rpc(port, method, params, id_=1, timeout=30):
    body = json.dumps({"jsonrpc": "2.0", "id": id_, "method": method,
                       "params": params}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/rpc", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


def _serve(tmp_path, runner):
    from spectre_tpu.prover_service.jobs import ensure_jobs
    from spectre_tpu.prover_service.rpc import serve

    class S:                                         # minimal state shim
        concurrency = 1
        params_dir = str(tmp_path)

    state = S()
    ensure_jobs(state, runner=runner)
    server = serve(state, port=0, background=True)
    return server, server.server_address[1], state


class TestManifestRpc:
    def test_contract_and_client(self, tmp_path):
        gate, started = threading.Event(), threading.Event()

        def runner(method, params):
            with prof.phase("prove/commit_advice"):
                started.set()
                gate.wait(10)
            return {"proof": "0xab"}

        server, port, state = _serve(tmp_path, runner)
        try:
            jid = _rpc(port, "submitProof_SyncStepCompressed",
                       {"w": 1})["result"]["job_id"]
            assert started.wait(10)
            # live -> -32002; unknown -> -32004
            err = _rpc(port, "getProofManifest", {"job_id": jid})["error"]
            assert err["code"] == -32002
            err = _rpc(port, "getProofManifest", {"job_id": "nope"})["error"]
            assert err["code"] == -32004
            gate.set()
            assert state.jobs.wait(jid, timeout=10).status == "done"

            man = _rpc(port, "getProofManifest", {"job_id": jid})["result"]
            assert man["schema"] == manifest.SCHEMA
            res = _rpc(port, "getProofResult", {"job_id": jid})["result"]
            assert res == {"proof": "0xab"}
            # manifest digest is checkably about THESE result bytes
            job = state.jobs.result(jid)
            assert man["result_digest"] == job.result_digest

            from spectre_tpu.prover_service.rpc_client import ProverClient
            cli = ProverClient(f"http://127.0.0.1:{port}/rpc")
            assert cli.get_manifest(jid) == man

            # corrupt the stored artifact -> -32006, result still serves
            path = state.jobs.store.path_for(job.manifest_digest,
                                             manifest.MANIFEST_SUFFIX)
            with open(path, "wb") as f:
                f.write(b"rotten bytes")
            err = _rpc(port, "getProofManifest", {"job_id": jid})["error"]
            assert err["code"] == -32006
            assert _rpc(port, "getProofResult",
                        {"job_id": jid})["result"] == {"proof": "0xab"}
        finally:
            gate.set()
            state.jobs.stop()
            server.shutdown()


class TestReportCli:
    def _write(self, tmp_path, name, **over):
        kw = dict(job_id=name, method="m",
                  submitted=1.0, admitted=1.1, started=1.2,
                  finished=3.2, queue_wait_s=0.1)
        kw.update(over)
        man = manifest.build(**kw)
        p = tmp_path / f"{name}.manifest.json"
        p.write_bytes(manifest.to_bytes(man))
        return p

    def test_render_from_file(self, tmp_path, capsys):
        from spectre_tpu.observability.__main__ import main
        p = self._write(tmp_path, "job-a")
        assert main(["report", str(p)]) == 0
        out = capsys.readouterr().out
        assert "job-a" in out and "queue wait" in out

    def test_diff_two_files(self, tmp_path, capsys):
        from spectre_tpu.observability.__main__ import main
        pa = self._write(tmp_path, "job-a")
        pb = self._write(tmp_path, "job-b", peak_rss_mb=64.0)
        assert main(["report", str(pa), "--diff", str(pb)]) == 0
        out = capsys.readouterr().out
        assert "diff job-a -> job-b" in out

    def test_fetch_by_job_id_over_rpc(self, tmp_path, capsys):
        from spectre_tpu.observability.__main__ import main
        server, port, state = _serve(tmp_path, _runner)
        try:
            jid = state.jobs.submit("m", {"w": 9})
            assert state.jobs.wait(jid, timeout=10).status == "done"
            rc = main(["report", jid,
                       "--url", f"http://127.0.0.1:{port}/rpc"])
            assert rc == 0
            out = capsys.readouterr().out
            assert jid in out and "prove" in out
        finally:
            state.jobs.stop()
            server.shutdown()

    def test_ci_gate_passes_within_thresholds(self, tmp_path, capsys):
        """ISSUE 10 satellite: `report BASELINE --diff CANDIDATE --ci`
        exits 0 when the candidate stays inside the regression budget."""
        from spectre_tpu.observability.__main__ import main
        base = self._write(tmp_path, "base")                 # prove_s 2.0
        cand = self._write(tmp_path, "cand", finished=3.3)   # +5%
        assert main(["report", str(base), "--diff", str(cand),
                     "--ci"]) == 0
        assert "CI gate: ok" in capsys.readouterr().out

    def test_ci_gate_fails_on_prove_regression(self, tmp_path, capsys):
        from spectre_tpu.observability.__main__ import main
        base = self._write(tmp_path, "base")                 # prove_s 2.0
        cand = self._write(tmp_path, "cand", finished=3.7)   # +25%
        assert main(["report", str(base), "--diff", str(cand),
                     "--ci"]) == 3
        assert "prove_s regressed" in capsys.readouterr().out
        # a loosened threshold admits the same candidate
        assert main(["report", str(base), "--diff", str(cand),
                     "--ci", "--max-prove-regress", "0.5"]) == 0

    def test_ci_gate_fails_on_new_compiles(self, tmp_path, capsys):
        """A compile on the warm path is a cache regression even when
        wall time still squeaks under the prove_s threshold."""
        from spectre_tpu.observability.__main__ import main
        base = self._write(tmp_path, "base")
        cand = self._write(
            tmp_path, "cand",
            compile_events=[{"event": compilelog.BACKEND_COMPILE,
                             "fn": "prove", "seconds": 0.5}])
        assert main(["report", str(base), "--diff", str(cand),
                     "--ci"]) == 3
        assert "compile.count regressed" in capsys.readouterr().out
        assert main(["report", str(base), "--diff", str(cand), "--ci",
                     "--max-compile-count-increase", "1"]) == 0

    def test_ci_requires_diff(self, tmp_path, capsys):
        from spectre_tpu.observability.__main__ import main
        base = self._write(tmp_path, "base")
        assert main(["report", str(base), "--ci"]) == 2
