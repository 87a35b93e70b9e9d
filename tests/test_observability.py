"""Observability tier (ISSUE 7): Prometheus exposition, per-job span
tracing, peak-RSS attribution.

Pins the acceptance gates:
  * GET /metrics is valid text exposition 0.0.4 whose counters match
    `HEALTH.snapshot()["counters"]` exactly (parity by construction —
    both read the same snapshot), including the
    `spectre_prove_latency_seconds` histogram;
  * `getTrace` returns well-formed Chrome trace-event JSON (nested "X"
    events) for a completed job, -32002 while it runs, -32004 when
    unknown;
  * histogram bucket math / conservative quantile pins (the p90 that
    prices `retry_after_s` must ignore the outlier a mean would not);
  * the RSS sampler thread self-terminates when the last job finishes
    (no leaked threads) and every finished job record carries
    `peak_rss_mb` through journal write AND replay.
"""

import json
import re
import threading
import time
import types
import urllib.request

import pytest

from spectre_tpu.observability import metrics as M
from spectre_tpu.observability import prom, tracing
from spectre_tpu.observability.rss import RssSampler, rss_mb
from spectre_tpu.utils import profiling as prof
from spectre_tpu.utils.health import HEALTH, ServiceHealth

# ---------------------------------------------------------------------------
# exposition parsing (strict: every non-comment line must be a sample)

_SAMPLE_RE = re.compile(
    r'^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(?P<labels>\{[^}]*\})? (?P<value>[^ ]+)$')


def _parse_exposition(text: str):
    """-> (samples {name{labels} -> float}, types {family -> type}).
    Raises on any line that is neither a comment nor a valid sample."""
    samples: dict[str, float] = {}
    types_: dict[str, str] = {}
    assert text.endswith("\n"), "exposition must end with a newline"
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, rest = line.partition("# TYPE ")
            fam, typ = rest.split(" ", 1)
            types_[fam] = typ
            continue
        if line.startswith("#"):
            assert line.startswith("# HELP ") or line.startswith("# TYPE "), \
                f"stray comment: {line!r}"
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"invalid sample line: {line!r}"
        key = m.group("name") + (m.group("labels") or "")
        samples[key] = float(m.group("value").replace("+Inf", "inf"))
    return samples, types_


# ---------------------------------------------------------------------------


class TestHistogram:
    def test_bucket_math_pins(self):
        h = M.Histogram("h", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.1, 0.5, 5.0, 50.0):
            h.observe(v)
        snap = h.snapshot()
        # le is INCLUSIVE: 0.1 lands in the le=0.1 bucket
        assert snap["buckets"] == [(0.1, 2), (1.0, 3), (10.0, 4),
                                   (float("inf"), 5)]
        assert snap["count"] == 5
        assert snap["sum"] == pytest.approx(55.65)

    def test_quantile_conservative_and_clamped(self):
        h = M.Histogram("h", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.1, 0.5, 5.0, 50.0):
            h.observe(v)
        # upper bound of the bucket where cumulative crosses q*count
        assert h.quantile(0.5) == 1.0
        assert h.quantile(0.8) == 10.0
        # overflow (+Inf has no edge): clamp to the largest finite bound
        assert h.quantile(1.0) == 10.0

    def test_quantile_empty(self):
        h = M.Histogram("h", buckets=(1.0,))
        assert h.quantile(0.9) is None
        assert h.quantile(0.9, default=3.5) == 3.5

    def test_registry_reregister_returns_existing(self):
        reg = M.MetricsRegistry()
        a = reg.histogram("x", buckets=(1.0,))
        b = reg.histogram("x", buckets=(2.0, 3.0))   # ignored: same series
        assert a is b
        assert reg.counter("c") is reg.counter("c")
        assert reg.gauge("g") is reg.gauge("g")

    def test_pull_gauge(self):
        reg = M.MetricsRegistry()
        g = reg.gauge("depth", fn=lambda: 7)
        assert g.value() == 7

    def test_histogram_vec_children_per_label(self):
        vec = M.HistogramVec("v", buckets=(1.0,), labelnames=("phase",))
        vec.labels(phase="a").observe(0.5)
        vec.labels(phase="b").observe(2.0)
        vec.labels(phase="a").observe(0.5)
        kids = vec.children()
        assert [k.labels for k in kids] == [{"phase": "a"}, {"phase": "b"}]
        assert kids[0].snapshot()["count"] == 2


class TestExposition:
    def test_counter_parity_with_health_snapshot(self):
        h = ServiceHealth()
        h.incr("jobs_done", 3)
        h.incr("prove_cpu_fallbacks_step")
        h.observe("prove_latency_s", 2.0)
        reg = M.MetricsRegistry()
        text = prom.render(health=h, registry=reg)
        samples, types_ = _parse_exposition(text)
        snap = h.snapshot()
        assert snap["counters"], "test needs at least one counter"
        for name, v in snap["counters"].items():
            key = f"spectre_{name}_total"
            assert samples[key] == v, key
            assert types_[key] == "counter"
        assert samples["spectre_mean_prove_latency_s"] == 2
        assert types_["spectre_uptime_seconds"] == "gauge"

    def test_histogram_family_rendering(self):
        reg = M.MetricsRegistry()
        hist = reg.histogram("spectre_t_seconds", "help text",
                             buckets=(1.0, 10.0))
        hist.observe(0.5)
        hist.observe(5.0)
        text = prom.render(health=ServiceHealth(), registry=reg)
        samples, types_ = _parse_exposition(text)
        assert types_["spectre_t_seconds"] == "histogram"
        assert samples['spectre_t_seconds_bucket{le="1"}'] == 1
        assert samples['spectre_t_seconds_bucket{le="10"}'] == 2
        assert samples['spectre_t_seconds_bucket{le="+Inf"}'] == 2
        assert samples["spectre_t_seconds_count"] == 2
        assert samples["spectre_t_seconds_sum"] == pytest.approx(5.5)
        # +Inf bucket always equals _count (Prometheus invariant)
        assert samples['spectre_t_seconds_bucket{le="+Inf"}'] == \
            samples["spectre_t_seconds_count"]

    def test_label_escaping(self):
        assert prom._esc('a"b\nc\\d') == r'a\"b\nc\\d'

    def test_table_lru_families(self, monkeypatch):
        """LRU stats render per cache; read via sys.modules so the scrape
        never imports jax itself — faked here to keep the test light."""
        import sys
        stats = {"hits": 4, "builds": 2, "evictions": 1, "recomputes": 1,
                 "bytes": 1024, "budget_bytes": 4096, "entries": 2}
        fake = types.SimpleNamespace(lru_stats=lambda: dict(stats))
        monkeypatch.setitem(sys.modules, "spectre_tpu.ops.msm", fake)
        text = prom.render(health=ServiceHealth(),
                           registry=M.MetricsRegistry())
        samples, _ = _parse_exposition(text)
        assert samples['spectre_table_lru_hits_total{cache="msm"}'] == 4
        assert samples['spectre_table_lru_recomputes_total{cache="msm"}'] == 1
        assert samples['spectre_table_lru_bytes{cache="msm"}'] == 1024


class TestTracing:
    def test_span_nesting_and_chrome_schema(self):
        with tracing.trace("t-nest") as tr:
            with prof.phase("a"):
                with prof.phase("b"):
                    time.sleep(0.002)
            with prof.phase("c"):
                pass
        ct = tracing.chrome_trace(tr)
        assert set(ct) == {"traceEvents", "displayTimeUnit", "otherData"}
        assert ct["displayTimeUnit"] == "ms"
        assert ct["otherData"]["trace_id"] == "t-nest"
        ev = ct["traceEvents"]
        assert [e["name"] for e in ev] == ["job", "a", "b", "c"]
        for e in ev:
            assert e["ph"] == "X"
            for k in ("ts", "dur", "pid", "tid", "cat"):
                assert k in e, (k, e)
        by = {e["name"]: e for e in ev}
        # containment: child interval inside parent interval
        for child, parent in (("a", "job"), ("b", "a"), ("c", "job")):
            assert by[parent]["ts"] <= by[child]["ts"]
            assert (by[child]["ts"] + by[child]["dur"]
                    <= by[parent]["ts"] + by[parent]["dur"] + 1e-3)

    def test_span_is_noop_without_trace(self):
        assert tracing.active() is None
        with tracing.span("orphan") as s:
            assert s is None
        with prof.phase("orphan-phase"):   # must not raise either
            pass

    def test_phase_seconds_sums_per_name_excluding_root(self):
        with tracing.trace("t-ps") as tr:
            with prof.phase("p"):
                time.sleep(0.002)
            with prof.phase("p"):
                time.sleep(0.002)
            with prof.phase("q"):
                pass
        ps = tracing.phase_seconds(tr)
        assert set(ps) == {"p", "q"}       # root span "job" excluded
        assert ps["p"] >= 0.004
        assert ps["p"] >= ps["q"]

    def test_annotate_exports_as_args(self):
        with tracing.trace("t-ann") as tr:
            with tracing.span("s"):
                tracing.annotate(cpu_fallback="oom")
        ev = {e["name"]: e for e in tracing.chrome_trace(tr)["traceEvents"]}
        assert ev["s"]["args"] == {"cpu_fallback": "oom"}

    def test_retention_ring_bounded(self, monkeypatch):
        monkeypatch.setenv(tracing.TRACE_KEEP_ENV, "2")
        tracing.reset()
        for i in range(3):
            with tracing.trace(f"ring-{i}"):
                pass
        assert tracing.get_trace("ring-0") is None      # evicted
        assert tracing.get_trace("ring-1") is not None
        assert tracing.get_trace("ring-2") is not None

    def test_file_sink_writes_chrome_json(self, tmp_path, monkeypatch):
        monkeypatch.setenv(tracing.TRACE_DIR_ENV, str(tmp_path))
        with tracing.trace("sink-job"):
            with prof.phase("p"):
                pass
        ct = json.loads((tmp_path / "sink-job.trace.json").read_text())
        assert [e["name"] for e in ct["traceEvents"]] == ["job", "p"]

    def test_file_sink_tolerates_unwritable_dir(self, tmp_path, monkeypatch):
        blocker = tmp_path / "not-a-dir"
        blocker.write_text("x")
        monkeypatch.setenv(tracing.TRACE_DIR_ENV,
                           str(blocker / "sub"))     # makedirs -> OSError
        with tracing.trace("sink-fail"):             # must not raise
            pass
        assert tracing.get_trace("sink-fail") is not None

    def test_nested_trace_restores_previous(self):
        with tracing.trace("outer") as outer:
            with tracing.trace("inner"):
                assert tracing.active().trace_id == "inner"
            assert tracing.active() is outer
        assert tracing.active() is None


class TestRssSampler:
    def test_lifecycle_no_leaked_threads(self):
        if rss_mb() is None:
            pytest.skip("no /proc/self/statm on this platform")
        s = RssSampler(interval_s=0.01)
        s.start("j1")
        th = s._thread
        assert th is not None and th.is_alive()
        ballast = bytearray(4 * 1024 * 1024)        # bump RSS by ~4MB
        time.sleep(0.05)                            # let it sample
        peak = s.finish("j1")
        del ballast
        assert peak is not None and peak > 1.0
        # the "no leaked threads" contract: last key out -> thread exits
        th.join(2.0)
        assert not th.is_alive()
        deadline = time.time() + 2.0
        while s._thread is not None and time.time() < deadline:
            time.sleep(0.01)
        assert s._thread is None

    def test_finish_unknown_key_is_none(self):
        s = RssSampler(interval_s=0.01)
        assert s.finish("nope") is None

    def test_peak_readable_while_active_and_respawn(self):
        if rss_mb() is None:
            pytest.skip("no /proc/self/statm on this platform")
        s = RssSampler(interval_s=0.01)
        s.start("a")
        assert s.peak("a") is not None and s.peak("a") > 1.0
        s.finish("a")
        time.sleep(0.05)
        s.start("b")                     # respawns after self-terminate
        assert s._thread is not None and s._thread.is_alive()
        assert s.finish("b") is not None


# ---------------------------------------------------------------------------
# JobQueue integration: p90 pricing, peak-RSS through journal + replay


def _ok_runner(method, params):
    with prof.phase("prove/commit_advice"):
        time.sleep(0.005)
    return {"proof": "0xab", "w": params.get("w")}


class TestQueueObservability:
    def test_retry_after_priced_by_p90_not_mean(self, tmp_path):
        """The satellite pin: one 500s outlier in ten proves drags the
        MEAN to 57.2s but the p90 bucket bound stays 10.0 — the shed
        hint must not punish every client for one pathological job."""
        from spectre_tpu.prover_service.jobs import JobQueue
        h = ServiceHealth()
        hist = M.queue_latency_histogram()
        lat = [8.0] * 9 + [500.0]
        for v in lat:
            hist.observe(v)
            h.observe("prove_latency_s", v)
        assert h.mean("prove_latency_s") == pytest.approx(57.2)
        q = JobQueue(_ok_runner, concurrency=1,
                     journal_dir=str(tmp_path), health=h, latency_hist=hist)
        assert q.retry_after_s() == 10.0          # p90, not ~57.2
        q.stop()

    def test_retry_after_empty_histogram_falls_back_to_mean(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobQueue
        h = ServiceHealth()
        h.observe("prove_latency_s", 15.0)
        q = JobQueue(_ok_runner, concurrency=1,
                     journal_dir=str(tmp_path), health=h)
        assert q.retry_after_s() == 15.0          # seed-pinned behavior
        q.stop()

    def test_job_carries_peak_rss_through_journal_and_replay(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobQueue
        if rss_mb() is None:
            pytest.skip("no /proc/self/statm on this platform")
        q = JobQueue(_ok_runner, concurrency=1, journal_dir=str(tmp_path))
        jid = q.submit("m", {"w": 1})
        job = q.wait(jid, timeout=10)
        assert job.status == "done"
        assert job.peak_rss_mb is not None and job.peak_rss_mb > 1.0
        assert q.status(jid)["peak_rss_mb"] == job.peak_rss_mb
        recs = [json.loads(l) for l in
                open(q.journal.path)]            # noqa: E741
        done = [r for r in recs if r.get("event") == "done"]
        assert done and done[0]["peak_rss_mb"] == job.peak_rss_mb
        q.stop()
        q2 = JobQueue(_ok_runner, concurrency=1, journal_dir=str(tmp_path))
        assert q2.result(jid).peak_rss_mb == job.peak_rss_mb
        q2.stop()

    def test_memory_shed_attributes_running_jobs(self, tmp_path):
        """A memory shed journals WHICH jobs were running and their
        running peaks; the record has no job_id so replay skips it."""
        from spectre_tpu.prover_service.jobs import JobQueue, \
            ServiceOverloaded
        if rss_mb() is None:
            pytest.skip("no /proc/self/statm on this platform")
        started, gate = threading.Event(), threading.Event()

        def runner(method, params):
            started.set()
            gate.wait(10)
            return {"proof": "0x01"}

        q = JobQueue(runner, concurrency=1, journal_dir=str(tmp_path),
                     mem_watermark_mb=0)          # admit the first job
        a = q.submit("m", {"w": "a"})
        assert started.wait(10)
        q.mem_watermark_mb = 1.0                  # now any submit sheds
        with pytest.raises(ServiceOverloaded, match="memory watermark"):
            q.submit("m", {"w": "b"})
        recs = [json.loads(l) for l in
                open(q.journal.path)]            # noqa: E741
        shed = [r for r in recs if r.get("event") == "shed_memory"]
        assert shed, recs
        assert "job_id" not in shed[-1]           # replay-safe
        running = shed[-1]["running"]
        assert [r["job_id"] for r in running] == [a]
        assert running[0]["peak_rss_mb"] > 1.0
        assert shed[-1]["rss_mb"] > 1.0
        gate.set()
        assert q.wait(a, timeout=10).status == "done"
        q.stop()
        q2 = JobQueue(runner, concurrency=1,      # replay tolerates record
                      journal_dir=str(tmp_path), mem_watermark_mb=0)
        assert q2.result(a).status == "done"
        q2.stop()

    def test_prove_latency_histogram_observes_completions(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobQueue
        c0 = M.PROVE_LATENCY.snapshot()["count"]
        q = JobQueue(_ok_runner, concurrency=1, journal_dir=str(tmp_path))
        jid = q.submit("m", {"w": 2})
        assert q.wait(jid, timeout=10).status == "done"
        q.stop()
        assert M.PROVE_LATENCY.snapshot()["count"] == c0 + 1


# ---------------------------------------------------------------------------
# end to end over HTTP: /metrics scrape parity + getTrace contract


def _rpc(port, method, params, id_=1, timeout=30):
    body = json.dumps({"jsonrpc": "2.0", "id": id_, "method": method,
                       "params": params}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/rpc", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


class TestServiceObservabilityHTTP:
    def _serve(self, tmp_path, runner):
        from spectre_tpu.prover_service.jobs import ensure_jobs
        from spectre_tpu.prover_service.rpc import serve

        class S:                                   # minimal state shim
            concurrency = 1
            params_dir = str(tmp_path)

        state = S()
        ensure_jobs(state, runner=runner)          # serve() reuses it
        server = serve(state, port=0, background=True)
        return server, server.server_address[1], state

    def test_get_trace_contract_and_metrics_parity(self, tmp_path):
        gate = threading.Event()
        started = threading.Event()

        def runner(method, params):
            with prof.phase("prove/commit_advice"):
                started.set()
                gate.wait(10)
            return {"proof": "0xab"}

        server, port, state = self._serve(tmp_path, runner)
        try:
            sub = _rpc(port, "submitProof_SyncStepCompressed", {"w": 1})
            jid = sub["result"]["job_id"]
            assert started.wait(10)
            # live job: trace not available yet -> JOB_NOT_DONE
            err = _rpc(port, "getTrace", {"job_id": jid})["error"]
            assert err["code"] == -32002
            # unknown job -> JOB_NOT_FOUND
            err = _rpc(port, "getTrace", {"job_id": "nope"})["error"]
            assert err["code"] == -32004
            gate.set()
            deadline = time.time() + 10
            while time.time() < deadline:
                st = _rpc(port, "getProofStatus", {"job_id": jid})["result"]
                if st["status"] == "done":
                    break
                time.sleep(0.02)
            assert st["status"] == "done"
            prss = st.get("peak_rss_mb")
            assert prss is None or prss > 1.0     # absent off-Linux only

            # -- getTrace: well-formed Chrome trace-event JSON -----------
            ct = _rpc(port, "getTrace", {"job_id": jid})["result"]
            names = [e["name"] for e in ct["traceEvents"]]
            assert names[0] == "job"
            assert "prove/commit_advice" in names
            assert all(e["ph"] == "X" for e in ct["traceEvents"])
            assert ct["otherData"]["trace_id"] == jid
            json.dumps(ct)                         # JSON-serializable

            # -- /metrics: exact counter parity with HEALTH.snapshot -----
            snap = HEALTH.snapshot()               # no RPCs after this
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/metrics")
            with urllib.request.urlopen(req, timeout=30) as resp:
                assert resp.headers["Content-Type"] == prom.CONTENT_TYPE
                text = resp.read().decode()
            samples, types_ = _parse_exposition(text)
            for name, v in snap["counters"].items():
                assert samples[f"spectre_{name}_total"] == v, name
            # the acceptance-gated histogram, with its invariant
            assert types_["spectre_prove_latency_seconds"] == "histogram"
            cnt = samples["spectre_prove_latency_seconds_count"]
            assert cnt >= 1
            assert samples[
                'spectre_prove_latency_seconds_bucket{le="+Inf"}'] == cnt
            # job gauges reflect the drained queue
            assert samples['spectre_jobs{status="done"}'] >= 1
            assert samples["spectre_job_workers"] == 1
        finally:
            gate.set()
            state.jobs.stop()
            server.shutdown()

    def test_queue_wait_and_compile_exposition_parity(self, tmp_path):
        """ISSUE-8 acceptance: /metrics exposes
        `spectre_queue_wait_seconds` and `spectre_compile_seconds{fn=}`
        with EXACT float parity against the manifest-derived values —
        one rounded float feeds every sink, so equality is ==, not
        approx. The compile event is driven through the listener
        directly (same plumbing jax.monitoring calls into)."""
        from spectre_tpu.observability import compilelog
        M.QUEUE_WAIT.reset()
        M.COMPILE_SECONDS.reset()

        def runner(method, params):
            with prof.phase("prove/quotient"):
                compilelog._listener(
                    "/jax/core/compile/backend_compile_duration",
                    1.23456789)
            return {"proof": "0xab"}

        server, port, state = self._serve(tmp_path, runner)
        try:
            jid = _rpc(port, "submitProof_SyncStepCompressed",
                       {"w": 1})["result"]["job_id"]
            assert state.jobs.wait(jid, timeout=10).status == "done"
            man = _rpc(port, "getProofManifest",
                       {"job_id": jid})["result"]
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics",
                    timeout=30) as resp:
                text = resp.read().decode()
            samples, types_ = _parse_exposition(text)

            assert types_["spectre_queue_wait_seconds"] == "histogram"
            assert samples["spectre_queue_wait_seconds_count"] == 1
            assert samples["spectre_queue_wait_seconds_sum"] \
                == man["queue_wait_s"]

            assert types_["spectre_compile_seconds"] == "histogram"
            assert man["compile"]["count"] == 1
            key = 'spectre_compile_seconds_count{fn="prove/quotient"}'
            assert samples[key] == man["compile"]["count"]
            key = 'spectre_compile_seconds_sum{fn="prove/quotient"}'
            assert samples[key] \
                == man["compile"]["by_fn"]["prove/quotient"]["seconds"] \
                == 1.234568
        finally:
            state.jobs.stop()
            server.shutdown()

    def test_rpc_client_helpers(self, tmp_path):
        from spectre_tpu.prover_service.rpc_client import ProverClient
        server, port, state = self._serve(tmp_path, _ok_runner)
        try:
            cli = ProverClient(f"http://127.0.0.1:{port}/rpc")
            text = cli.metrics_text()
            samples, _ = _parse_exposition(text)
            assert "spectre_uptime_seconds" in samples
            jid = state.jobs.submit("m", {"w": 9})
            assert state.jobs.wait(jid, timeout=10).status == "done"
            ct = cli.get_trace(jid)
            assert ct["otherData"]["trace_id"] == jid
        finally:
            state.jobs.stop()
            server.shutdown()


class TestCompileAttribution:
    """ISSUE 16 satellite: compile telemetry attributes each cache miss to
    the INNERMOST open `compilelog.entry_point`, not the parent phase —
    a two-level entry (sharded runner inside a prove phase) books its
    compile under its own name, and the span fallback still holds when no
    entry point is open."""

    def test_two_level_entry_points_per_function_counts(self):
        import jax
        import jax.numpy as jnp

        from spectre_tpu.observability import compilelog

        assert compilelog.install()
        # fresh lambdas => guaranteed trace-cache misses for each level
        outer_fn = jax.jit(lambda v: v + jnp.uint32(1))
        inner_fn = jax.jit(lambda v: v * jnp.uint32(3))
        x = jnp.arange(8, dtype=jnp.uint32)
        with tracing.trace("attr-two-level"), tracing.span("prove/phase"):
            with compilelog.capture() as events:
                with compilelog.entry_point("runner.outer"):
                    outer_fn(x).block_until_ready()
                    with compilelog.entry_point("runner.inner"):
                        inner_fn(x).block_until_ready()
                    # warm second calls: zero new events at either level
                    outer_fn(x).block_until_ready()
                    with compilelog.entry_point("runner.inner"):
                        inner_fn(x).block_until_ready()
        s = compilelog.summarize(events)
        assert s["by_fn"]["runner.outer"]["count"] == 1
        assert s["by_fn"]["runner.inner"]["count"] == 1
        # nothing leaked into the parent phase span's bucket
        assert "prove/phase" not in s["by_fn"]
        assert s["count"] == 2

    def test_span_fallback_without_entry_point(self):
        import jax
        import jax.numpy as jnp

        from spectre_tpu.observability import compilelog

        assert compilelog.install()
        fn = jax.jit(lambda v: v - jnp.uint32(7))
        x = jnp.arange(8, dtype=jnp.uint32)
        with tracing.trace("attr-fallback"), tracing.span("prove/fallback"):
            with compilelog.capture() as events:
                fn(x).block_until_ready()
        s = compilelog.summarize(events)
        assert list(s["by_fn"]) == ["prove/fallback"]
        assert s["by_fn"]["prove/fallback"]["count"] == 1


class TestIntegrityCounters:
    """ISSUE 9 pin: every output-integrity counter rides the existing
    ServiceHealth -> /healthz -> /metrics bridge — each appears in the
    exposition as spectre_<name>_total with exact snapshot parity."""

    COUNTERS = ("proofs_verified", "proofs_verify_failed",
                "proofs_sdc_retried", "self_check_failures",
                "artifacts_scrubbed", "artifacts_scrub_corrupt",
                "artifacts_expired")

    def test_new_counters_render_with_parity(self):
        h = ServiceHealth()
        for i, name in enumerate(self.COUNTERS, start=1):
            h.incr(name, i)
        text = prom.render(health=h, registry=M.MetricsRegistry())
        samples, types_ = _parse_exposition(text)
        snap = h.snapshot()["counters"]
        for i, name in enumerate(self.COUNTERS, start=1):
            key = f"spectre_{name}_total"
            assert samples[key] == i == snap[name], key
            assert types_[key] == "counter"

    def test_self_verify_phase_in_histogram_vec(self):
        # the prove/self_verify span cost lands in the same
        # spectre_phase_seconds{phase=} family every other phase uses
        from spectre_tpu.observability.metrics import PHASE_SECONDS
        PHASE_SECONDS.labels(phase="prove/self_verify").observe(0.001)
        kids = PHASE_SECONDS.children()
        assert any(k.labels == {"phase": "prove/self_verify"} for k in kids)
