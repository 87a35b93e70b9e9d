"""Service layer: preprocessor converters, RPC plumbing, contracts, fixtures."""

import dataclasses
import json
import threading
import urllib.request

import pytest

from spectre_tpu import spec as SP
from spectre_tpu.contracts import MockVerifier, SpectreContract
from spectre_tpu.contracts.spectre import StepInput
from spectre_tpu.models import CommitteeUpdateCircuit, StepCircuit
from spectre_tpu.preprocessor.rotation import rotation_args_from_update
from spectre_tpu.preprocessor.step import step_args_from_finality_update
from spectre_tpu.prover_service.calldata import decode_calldata, encode_calldata
from spectre_tpu.witness import (
    default_committee_update_args,
    default_sync_step_args,
)
from spectre_tpu.test_utils import (
    dump_rotation_fixture,
    dump_step_fixture,
    load_rotation_fixture,
    load_step_fixture,
)

TINY = dataclasses.replace(SP.MINIMAL, name="tiny", sync_committee_size=2)


def _hdr_dict(h):
    return {"slot": h.slot, "proposer_index": h.proposer_index,
            "parent_root": "0x" + h.parent_root.hex(),
            "state_root": "0x" + h.state_root.hex(),
            "body_root": "0x" + h.body_root.hex()}


class TestPreprocessor:
    def test_step_converter_roundtrip(self):
        from spectre_tpu.fields import bls12_381 as bls
        args = default_sync_step_args(TINY)
        pks = [bls.g1_compress((bls.Fq(x), bls.Fq(y)))
               for x, y in args.pubkeys_uncompressed]
        update = {
            "attested_header": _hdr_dict(args.attested_header),
            "finalized_header": _hdr_dict(args.finalized_header),
            "finality_branch": ["0x" + b.hex() for b in args.finality_branch],
            "execution_payload_root": "0x" + args.execution_payload_root.hex(),
            "execution_branch": ["0x" + b.hex() for b in args.execution_payload_branch],
            "sync_aggregate": {
                "sync_committee_bits": args.participation_bits,
                "sync_committee_signature": "0x" + args.signature_compressed.hex(),
            },
        }
        rebuilt = step_args_from_finality_update(
            update, pks, args.domain, TINY)
        assert rebuilt.signing_root() == args.signing_root()
        assert StepCircuit.get_instances(rebuilt, TINY) == \
            StepCircuit.get_instances(args, TINY)

    def test_step_converter_rejects_bad_branch(self):
        args = default_sync_step_args(TINY)
        update = {
            "attested_header": _hdr_dict(args.attested_header),
            "finalized_header": _hdr_dict(args.finalized_header),
            "finality_branch": ["0x" + b"\x00".hex() * 32
                                for _ in args.finality_branch],
            "execution_payload_root": "0x" + args.execution_payload_root.hex(),
            "execution_branch": ["0x" + b.hex() for b in args.execution_payload_branch],
            "sync_aggregate": {"sync_committee_bits": args.participation_bits,
                               "sync_committee_signature": "0x" + args.signature_compressed.hex()},
        }
        with pytest.raises(AssertionError, match="finality branch"):
            step_args_from_finality_update(update, [], args.domain, TINY)

    def test_rotation_converter_with_branch_extension(self):
        from spectre_tpu.fields import bls12_381 as bls
        from spectre_tpu.witness.types import bytes48_root
        from spectre_tpu.gadgets.ssz_merkle import sha256_pair_native
        from spectre_tpu.witness.rotation import mock_root
        args = default_committee_update_args(TINY)
        # craft an update whose branch is the container-depth branch: the
        # converter must extend it with the aggregate-pubkey sibling
        agg = bls.g1_compress(bls.sk_to_pk(999))
        full_branch = [bytes48_root(agg)] + [bytes([d]) * 32
                                             for d in range(TINY.sync_committee_depth)]
        state_root = mock_root(args.committee_pubkeys_root(), full_branch,
                               TINY.sync_committee_pubkeys_root_index)
        hdr = dataclasses.replace(args.finalized_header, state_root=state_root)
        update = {
            "finalized_header": _hdr_dict(hdr),
            "next_sync_committee": {
                "pubkeys": ["0x" + pk.hex() for pk in args.pubkeys_compressed],
                "aggregate_pubkey": "0x" + agg.hex(),
            },
            "next_sync_committee_branch": ["0x" + b.hex() for b in full_branch[1:]],
        }
        rebuilt = rotation_args_from_update(update, TINY)
        assert len(rebuilt.sync_committee_branch) == TINY.sync_committee_pubkeys_depth


class TestCalldata:
    def test_roundtrip(self):
        inst = [123, 456]
        proof = b"\xAB" * 100
        data = encode_calldata(inst, proof)
        got_inst, got_proof = decode_calldata(data, 2)
        assert (got_inst, got_proof) == (inst, proof)


class TestFixtures:
    def test_step_fixture_roundtrip(self, tmp_path):
        args = default_sync_step_args(TINY)
        p = str(tmp_path / "step.json")
        dump_step_fixture(args, p)
        back = load_step_fixture(p)
        assert back == args

    def test_rotation_fixture_roundtrip(self, tmp_path):
        args = default_committee_update_args(TINY)
        p = str(tmp_path / "rot.json")
        dump_rotation_fixture(args, p)
        assert load_rotation_fixture(p) == args


class TestSpectreContract:
    """Protocol tests with MockVerifiers (reference `contract-tests/tests/
    spectre.rs:34-110` — multi-system testing without an EVM)."""

    def _contract(self, period=0):
        return SpectreContract(spec=TINY, initial_sync_period=period,
                               initial_committee_poseidon=12345)

    def test_step_advances_head(self):
        args = default_sync_step_args(TINY)
        c = self._contract(TINY.sync_period(args.attested_header.slot))
        inp = StepInput(
            attested_slot=args.attested_header.slot,
            finalized_slot=args.finalized_header.slot,
            participation=sum(args.participation_bits),
            finalized_header_root=args.finalized_header.hash_tree_root(),
            execution_payload_root=args.execution_payload_root)
        c.step(inp, b"")
        assert c.head == args.finalized_header.slot
        assert c.block_header_roots[inp.finalized_slot] == inp.finalized_header_root

    def test_step_input_encoding_matches_circuit(self):
        # Solidity toPublicInputsCommitment == circuit get_instances[0]
        # (reference `step_input_encoding.rs:109-116`)
        args = default_sync_step_args(TINY)
        inp = StepInput(
            attested_slot=args.attested_header.slot,
            finalized_slot=args.finalized_header.slot,
            participation=sum(args.participation_bits),
            finalized_header_root=args.finalized_header.hash_tree_root(),
            execution_payload_root=args.execution_payload_root)
        assert inp.to_public_inputs_commitment() == \
            StepCircuit.get_instances(args, TINY)[0]

    def test_step_rejects_low_participation(self):
        c = self._contract(TINY.sync_period(10))
        inp = StepInput(attested_slot=10, finalized_slot=9, participation=1,
                        finalized_header_root=b"\x00" * 32,
                        execution_payload_root=b"\x00" * 32)
        with pytest.raises(AssertionError, match="participation"):
            c.step(inp, b"")

    def test_rotate_flow(self):
        c = self._contract()
        args = default_committee_update_args(TINY)
        fin_slot = args.finalized_header.slot
        root = args.finalized_header.hash_tree_root()
        c.block_header_roots[fin_slot] = root
        inst = CommitteeUpdateCircuit.get_instances(args, TINY)
        c.rotate(fin_slot, inst[0], inst[1], inst[2], b"")
        next_period = TINY.sync_period(fin_slot) + 1
        assert c.sync_committee_poseidons[next_period] == inst[0]
        # double rotation refused
        with pytest.raises(AssertionError, match="already rotated"):
            c.rotate(fin_slot, inst[0], inst[1], inst[2], b"")

    def test_rotate_rejects_wrong_root(self):
        c = self._contract()
        c.block_header_roots[100] = b"\x01" * 32
        with pytest.raises(AssertionError, match="header root mismatch"):
            c.rotate(100, 1, 2, 3, b"")


class _FakeState:
    """Canned prover for RPC plumbing tests (real proving is minutes)."""

    def __init__(self, spec, concurrency=1, delay=0.0, gate=None):
        self.spec = spec
        self.concurrency = concurrency
        self.delay = delay
        self.gate = gate        # zero-arg callable run while "proving"
        self.active = 0
        self.max_active = 0
        self._lock = threading.Lock()

    def _track(self):
        import contextlib
        import time

        @contextlib.contextmanager
        def cm():
            with self._lock:
                self.active += 1
                self.max_active = max(self.max_active, self.active)
            try:
                if self.delay:
                    time.sleep(self.delay)
                if self.gate is not None:
                    self.gate()
                yield
            finally:
                with self._lock:
                    self.active -= 1
        return cm()

    def prove_step(self, args):
        with self._track():
            return b"\x01" * 64, StepCircuit.get_instances(args, self.spec)

    def prove_committee(self, args):
        with self._track():
            return (b"\x02" * 64,
                    CommitteeUpdateCircuit.get_instances(args, self.spec))


class TestBatchProveAPI:
    def test_batch_preserves_order_and_concurrency(self):
        """prove_*_batch: the DP governor maps requests over a pool sized by
        the configured concurrency, results in request order (the proving
        itself is exercised by the prover tests; this pins the batch API)."""
        import threading
        import time

        from spectre_tpu.prover_service.state import ProverState

        seen = []

        class S(ProverState):
            def __init__(self):
                self.concurrency = 2

            def prove_step(self, args):
                seen.append((args, threading.get_ident()))
                time.sleep(0.02)
                return (b"proof-%d" % args, [args])

        s = S()
        out = s.prove_step_batch([3, 1, 2])
        assert out == [(b"proof-3", [3]), (b"proof-1", [1]),
                       (b"proof-2", [2])]
        assert len({t for _, t in seen}) >= 2   # ran on >1 worker


def _step_request_params(args):
    from spectre_tpu.fields import bls12_381 as bls
    pks = [("0x" + bls.g1_compress((bls.Fq(x), bls.Fq(y))).hex())
           for x, y in args.pubkeys_uncompressed]
    update = {
        "attested_header": _hdr_dict(args.attested_header),
        "finalized_header": _hdr_dict(args.finalized_header),
        "finality_branch": ["0x" + b.hex() for b in args.finality_branch],
        "execution_payload_root": "0x" + args.execution_payload_root.hex(),
        "execution_branch": ["0x" + b.hex()
                             for b in args.execution_payload_branch],
        "sync_aggregate": {
            "sync_committee_bits": args.participation_bits,
            "sync_committee_signature": "0x" + args.signature_compressed.hex(),
        },
    }
    return {"light_client_finality_update": update, "pubkeys": pks,
            "domain": "0x" + args.domain.hex()}


def _rpc_post(port, payload, raw=None, timeout=600):
    body = raw if raw is not None else json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/rpc", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


class TestRPC:
    def test_rpc_roundtrip(self):
        from spectre_tpu.prover_service.rpc import serve
        state = _FakeState(TINY)
        server = serve(state, port=0, background=True)
        port = server.server_address[1]
        try:
            args = default_sync_step_args(TINY)
            data = _rpc_post(port, {
                "jsonrpc": "2.0", "id": 1,
                "method": "genEvmProof_SyncStepCompressed",
                "params": _step_request_params(args)})
            assert "result" in data, data
            want = StepCircuit.get_instances(args, TINY)
            assert [int(v, 16) for v in data["result"]["instances"]] == want
            # unknown method -> JSON-RPC error
            data2 = _rpc_post(port, {"jsonrpc": "2.0", "id": 2,
                                     "method": "nope", "params": {}},
                              timeout=60)
            assert data2["error"]["code"] == -32601
        finally:
            server.shutdown()

    def test_error_taxonomy(self):
        """Parsing, envelope validation and dispatch are separate failure
        domains (ISSUE-3 satellite): malformed JSON is -32700, a non-dict
        or jsonrpc-less body -32600, and an internal prover error -32603
        with a sanitized message — never a bogus 'parse error'."""
        from spectre_tpu.prover_service.rpc import serve

        class Boom(_FakeState):
            def prove_step(self, args):
                raise RuntimeError("secret internal path /opt/x leaked")

        server = serve(Boom(TINY), port=0, background=True)
        port = server.server_address[1]
        try:
            # malformed JSON -> parse error
            data = _rpc_post(port, None, raw=b"{nope", timeout=60)
            assert data["error"]["code"] == -32700
            # valid JSON, not an object -> invalid request
            data = _rpc_post(port, [1, 2, 3], timeout=60)
            assert data["error"]["code"] == -32600
            # object without jsonrpc member -> invalid request
            data = _rpc_post(port, {"method": "ping", "id": 1}, timeout=60)
            assert data["error"]["code"] == -32600
            # dispatch blow-up -> internal error, sanitized (class name
            # only, no exception text on the wire)
            args = default_sync_step_args(TINY)
            data = _rpc_post(port, {
                "jsonrpc": "2.0", "id": 4,
                "method": "genEvmProof_SyncStepCompressed",
                "params": _step_request_params(args)})
            assert data["error"]["code"] == -32603
            assert "secret internal path" not in data["error"]["message"]
            assert "RuntimeError" in data["error"]["message"]
            # missing params -> invalid params, not internal error
            data = _rpc_post(port, {
                "jsonrpc": "2.0", "id": 5,
                "method": "genEvmProof_SyncStepCompressed", "params": {}},
                timeout=60)
            assert data["error"]["code"] == -32602
        finally:
            server.shutdown()


class TestAsyncRPC:
    def test_submit_poll_result_matches_blocking(self):
        """ISSUE-3 acceptance: submit -> poll -> result equals the blocking
        genEvmProof_* result for the same witness (and dedups onto the
        same job)."""
        from spectre_tpu.prover_service.rpc import serve
        state = _FakeState(TINY)
        server = serve(state, port=0, background=True)
        port = server.server_address[1]
        try:
            args = default_sync_step_args(TINY)
            params = _step_request_params(args)
            blocking = _rpc_post(port, {
                "jsonrpc": "2.0", "id": 1,
                "method": "genEvmProof_SyncStepCompressed",
                "params": params})["result"]
            sub = _rpc_post(port, {
                "jsonrpc": "2.0", "id": 2,
                "method": "submitProof_SyncStepCompressed",
                "params": params})["result"]
            jid = sub["job_id"]
            # same witness digest -> dedup onto the already-proved job
            assert sub["status"] == "done"
            for _ in range(100):
                st = _rpc_post(port, {"jsonrpc": "2.0", "id": 3,
                                      "method": "getProofStatus",
                                      "params": {"job_id": jid}},
                               timeout=60)["result"]
                if st["status"] in ("done", "failed"):
                    break
                import time
                time.sleep(0.05)
            assert st["status"] == "done"
            result = _rpc_post(port, {"jsonrpc": "2.0", "id": 4,
                                      "method": "getProofResult",
                                      "params": {"job_id": jid}},
                               timeout=60)["result"]
            assert result == blocking
            # unknown job id -> typed error
            err = _rpc_post(port, {"jsonrpc": "2.0", "id": 5,
                                   "method": "getProofResult",
                                   "params": {"job_id": "nope"}},
                            timeout=60)["error"]
            assert err["code"] == -32004
        finally:
            server.shutdown()

    def test_concurrent_submits_respect_cap(self):
        """N async submissions drain at the configured concurrency: the
        worker-pool size mirrors ProverState's semaphore cap."""
        import itertools

        from spectre_tpu.prover_service.jobs import ensure_jobs
        # the first two proves meet at a barrier INSIDE the tracked
        # section, so max_active == 2 is decided by the pool size and not
        # by how a sleep interleaves under a loaded scheduler
        both_in = threading.Barrier(2)
        arrivals = itertools.count()

        def gate():
            if next(arrivals) < 2:
                both_in.wait(timeout=30)

        state = _FakeState(TINY, concurrency=2, gate=gate)
        runner_calls = []

        def runner(method, params):
            runner_calls.append(method)
            _, inst = state.prove_step(default_sync_step_args(TINY))
            return {"instances": [hex(v) for v in inst]}

        q = ensure_jobs(state, runner=runner)
        jids = [q.submit("m", {"w": i}) for i in range(6)]
        for jid in jids:
            assert q.wait(jid, timeout=30).status == "done"
        assert len(runner_calls) == 6
        assert state.max_active <= 2       # cap honored
        assert state.max_active == 2       # ...and actually used
        q.stop()

    def test_healthz_endpoint(self):
        from spectre_tpu.prover_service.rpc import serve
        state = _FakeState(TINY)
        server = serve(state, port=0, background=True)
        port = server.server_address[1]
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=60) as resp:
                data = json.load(resp)
            assert data["status"] == "ok"
            assert "counters" in data and "jobs" in data
            # the RPC method view carries the same counters
            h = _rpc_post(port, {"jsonrpc": "2.0", "id": 1,
                                 "method": "health", "params": {}},
                          timeout=60)["result"]
            assert "counters" in h
            assert "beacon_breakers" in h
        finally:
            server.shutdown()

    def test_healthz_not_ready_when_breaker_open(self):
        """ROADMAP PR-3 follow-up (ISSUE 4 satellite): an OPEN beacon
        circuit breaker turns the readiness probe into a 503 with the
        breaker state in the body; once the breaker leaves the open state
        (cooldown -> half-open trial) readiness returns to 200."""
        import urllib.error

        from spectre_tpu.preprocessor.beacon import (BeaconClient,
                                                     CircuitBreakerOpen)
        from spectre_tpu.prover_service.rpc import serve
        from spectre_tpu.utils import faults
        state = _FakeState(TINY)
        server = serve(state, port=0, background=True)
        port = server.server_address[1]
        client = BeaconClient("http://127.0.0.1:9/", retries=0,
                              breaker_threshold=1, breaker_cooldown=0.2,
                              total_timeout=5.0, sleep=lambda _s: None)
        # the breaker's clock is the test's (the injectable clock the fault
        # tier uses): "open" lasts through the HTTP round trip below
        # however loaded the host is
        now = [1000.0]
        client._breaker._clock = lambda: now[0]
        try:
            faults.install_plan("beacon.fetch:connreset:1")
            # threshold=1: the injected failure trips the breaker mid-call
            with pytest.raises(CircuitBreakerOpen):
                client._get("/eth/v1/anything")
            assert client.breaker_state == "open"
            req = urllib.request.Request(f"http://127.0.0.1:{port}/healthz")
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=60)
            assert e.value.code == 503
            body = json.load(e.value)
            assert body["status"] == "degraded"
            assert any(b["state"] == "open"
                       for b in body["beacon_breakers"])
            # cooldown elapses -> half-open admits a trial -> ready again
            now[0] += 0.25
            assert client.breaker_state == "half-open"
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=60) as resp:
                data = json.load(resp)
            assert data["status"] == "ok"
        finally:
            faults.clear()
            del client
            server.shutdown()


class TestProverClient:
    def test_typed_rpc_error(self):
        from spectre_tpu.prover_service.rpc import serve
        from spectre_tpu.prover_service.rpc_client import ProverClient, RpcError
        server = serve(_FakeState(TINY), port=0, background=True)
        port = server.server_address[1]
        try:
            client = ProverClient(f"http://127.0.0.1:{port}/rpc", timeout=60)
            assert client.ping() == "pong"
            with pytest.raises(RpcError) as e:
                client._call("definitelyNotAMethod", {})
            assert e.value.code == -32601
            assert "unknown method" in e.value.message
        finally:
            server.shutdown()

    def test_retries_once_on_connection_reset(self, monkeypatch):
        from spectre_tpu.prover_service import rpc_client as rc
        calls = []

        class _Resp:
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

            def read(self):
                return json.dumps({"jsonrpc": "2.0", "result": "pong",
                                   "id": 1}).encode()

        def flaky(req, timeout=None):
            calls.append(timeout)
            if len(calls) == 1:
                raise ConnectionResetError("injected reset")
            return _Resp()

        monkeypatch.setattr(rc.urllib.request, "urlopen", flaky)
        client = rc.ProverClient("http://127.0.0.1:1/rpc", timeout=5)
        assert client.ping() == "pong"
        assert len(calls) == 2             # one reset, one retry
        # a second reset in a row (fresh call) still fails after the
        # single retry
        calls.clear()

        def always_reset(req, timeout=None):
            calls.append(timeout)
            raise ConnectionResetError("injected reset")

        monkeypatch.setattr(rc.urllib.request, "urlopen", always_reset)
        with pytest.raises(ConnectionResetError):
            client.ping()
        # two prove attempts, then ONE membership probe (ISSUE 18: the
        # exhausted rotation asks `health` for fresh replica URLs before
        # failing hard; here it resets too, so the original error wins)
        assert len(calls) == 3

    def test_refreshes_endpoints_from_membership_when_exhausted(
            self, monkeypatch):
        """ISSUE-18 satellite: once the conn-reset rotation has burned
        every configured URL, the client asks the dispatcher membership
        (`health` RPC) for replica URLs it doesn't know yet and retries
        against the adopted fleet before failing hard."""
        from spectre_tpu.prover_service import rpc_client as rc
        calls = []
        fresh = "http://127.0.0.1:7103"

        class _Resp:
            def __init__(self, payload):
                self._payload = payload

            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

            def read(self):
                return json.dumps(self._payload).encode()

        def fake(req, timeout=None):
            url, body = req.full_url, json.loads(req.data)
            calls.append((url, body["method"]))
            if body["method"] == "health":
                if url == fresh:
                    raise ConnectionResetError("still dead")
                return _Resp({"jsonrpc": "2.0", "id": 1, "result": {
                    "dispatcher": {"replicas": [
                        {"replica_id": "r-new", "url": fresh},
                        {"replica_id": "r-old",
                         "url": "http://127.0.0.1:7101"}]}}})
            if url == fresh:
                return _Resp({"jsonrpc": "2.0", "result": "pong", "id": 1})
            raise ConnectionResetError("injected reset")

        monkeypatch.setattr(rc.urllib.request, "urlopen", fake)
        client = rc.ProverClient(["http://127.0.0.1:7101",
                                  "http://127.0.0.1:7102"],
                                 timeout=5, conn_retries=1,
                                 sleep=lambda s: None)
        assert client.ping() == "pong"
        assert client.endpoint_refreshes == 1
        assert client.urls[-1] == fresh       # adopted, not replaced
        assert client.url == fresh            # and now current
        # the already-known url in the snapshot was NOT duplicated
        assert client.urls.count("http://127.0.0.1:7101") == 1
        # ping: reset on 7101, rotate-reset on 7102, health probe, retry
        assert [m for _, m in calls].count("health") == 1

    def test_refresh_failure_still_raises(self, monkeypatch):
        """When no endpoint serves a membership snapshot the original
        conn-reset surfaces unchanged — no infinite refresh loop."""
        from spectre_tpu.prover_service import rpc_client as rc

        def always_reset(req, timeout=None):
            raise ConnectionResetError("injected reset")

        monkeypatch.setattr(rc.urllib.request, "urlopen", always_reset)
        client = rc.ProverClient(["http://127.0.0.1:7101",
                                  "http://127.0.0.1:7102"],
                                 timeout=5, conn_retries=1,
                                 sleep=lambda s: None)
        with pytest.raises(ConnectionResetError):
            client.ping()
        assert client.endpoint_refreshes == 0

    def test_get_update_cached_honors_304(self, tmp_path):
        """ISSUE-14 satellite: the client-side digest cache sends
        If-None-Match and re-serves the cached decode on 304, so a
        sealed update crosses the wire at most once per client."""
        from spectre_tpu.follower.updates import UpdateStore
        from spectre_tpu.gateway import Gateway
        from spectre_tpu.prover_service.rpc import serve
        from spectre_tpu.prover_service.rpc_client import (ProverClient,
                                                           RpcError)
        store = UpdateStore(str(tmp_path))
        for p in range(3, 8):
            store.append_committee(p, {"proof": "0x" + "ab" * 8,
                                       "committee_poseidon": hex(p * 7 + 1),
                                       "instances": [hex(p)]})
        server = serve(_FakeState(TINY), port=0, background=True,
                       gateway=Gateway(store, pack_periods=2))
        port = server.server_address[1]
        try:
            client = ProverClient(f"http://127.0.0.1:{port}/rpc",
                                  timeout=60)
            first = client.get_update_cached(4)
            assert first["period"] == 4
            assert client.cache_304s == 0
            assert client.get_update_cached(4) == first   # revalidated
            assert client.cache_304s == 1
            rng = client.get_update_range_cached(3, count=3)
            assert [u["period"] for u in rng["updates"]] == [3, 4, 5]
            assert client.get_update_range_cached(3, count=3) == rng
            assert client.cache_304s == 2
            boot = client.get_bootstrap_cached()
            assert boot["anchor_period"] == 3 and boot["tip_period"] == 7
            with pytest.raises(RpcError) as e:
                client.get_update_cached(99)
            assert e.value.code == -32007
            # distinct keys stay independently cached; the 404 does not
            assert len(client._etag_cache) == 3
        finally:
            server.shutdown()

    def test_gateway_routes_404_without_mount(self):
        """GET /v1/* on a server launched without --gateway is a plain
        404, not a crash in the RPC handler."""
        import urllib.error
        import urllib.request
        from spectre_tpu.prover_service.rpc import serve
        server = serve(_FakeState(TINY), port=0, background=True)
        port = server.server_address[1]
        try:
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/v1/bootstrap", timeout=30)
            assert e.value.code == 404
        finally:
            server.shutdown()


class TestWaitForProofDeadline:
    """ISSUE 10 satellite: ONE overall deadline bounds wait_for_proof —
    slow HTTP round trips, per-poll timeouts and overload-retry sleeps
    all count against it, so a slow server cannot stretch the wait."""

    def _client(self, clk, **kw):
        from spectre_tpu.prover_service.rpc_client import ProverClient

        def sleep(s):
            clk["t"] += s
        kw.setdefault("rng", lambda: 0.0)   # no jitter: deterministic
        return ProverClient("http://127.0.0.1:1/rpc", timeout=3600,
                            sleep=sleep, clock=lambda: clk["t"], **kw)

    def test_slow_polls_cannot_stretch_past_deadline(self):
        clk = {"t": 0.0}
        client = self._client(clk)
        seen_timeouts = []

        def slow_call(method, params, timeout=None):
            seen_timeouts.append(timeout)
            clk["t"] += 40.0            # each HTTP round trip eats 40 s
            return {"status": "running"}

        client._call = slow_call
        with pytest.raises(TimeoutError, match="still running"):
            client.wait_for_proof("j1", poll=1.0, timeout=100.0)
        # polls at t=0/41/82; t=123 > 100 so NO fourth poll starts
        assert len(seen_timeouts) == 3
        assert clk["t"] < 130.0
        # per-call HTTP timeout is clamped to the time remaining
        assert seen_timeouts[0] == 30.0            # min(3600, 30, 100)
        assert seen_timeouts[2] == pytest.approx(18.0)   # 100 - 82 left

    def test_overload_backoff_capped_by_deadline(self):
        from spectre_tpu.prover_service.rpc_client import RpcError
        clk = {"t": 0.0}
        client = self._client(clk, retry_after_cap=100.0)
        calls = []

        def shedding_call(method, params, timeout=None):
            calls.append(clk["t"])
            raise RpcError(-32001, "service overloaded", retry_after=50.0)

        client._call = shedding_call
        with pytest.raises(RpcError) as e:
            client.wait_for_proof("j1", poll=1.0, timeout=60.0)
        assert e.value.code == -32001
        # first shed sleeps its 50 s hint (fits); the second backoff
        # would land at t=100 > 60 so the error surfaces immediately
        assert calls == [0.0, 50.0]
        assert clk["t"] == 50.0                    # never slept past deadline

    def test_no_timeout_waits_indefinitely(self):
        clk = {"t": 0.0}
        client = self._client(clk)
        states = iter(["queued", "running", "done"])

        def call(method, params, timeout=None):
            if method == "getProofStatus":
                return {"status": next(states)}
            return {"proof": "0x01"}

        client._call = call
        assert client.wait_for_proof("j1", poll=1.0)["proof"] == "0x01"


class TestOverloadRPC:
    """ISSUE 6: a shed submission surfaces as HTTP 429 + Retry-After on
    the transport AND `-32001 service overloaded` (with data.retry_after_s)
    in the JSON-RPC envelope; the typed client honors the hint."""

    def _overloaded_server(self):
        # queue_depth=0: every fresh submission sheds (deterministic)
        from spectre_tpu.prover_service.rpc import serve
        server = serve(_FakeState(TINY), port=0, background=True,
                       queue_depth=0)
        return server, server.server_address[1]

    def test_429_retry_after_and_rpc_envelope(self):
        import urllib.error
        server, port = self._overloaded_server()
        try:
            body = json.dumps({
                "jsonrpc": "2.0", "id": 1,
                "method": "submitProof_SyncStepCompressed",
                "params": _step_request_params(
                    default_sync_step_args(TINY))}).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/rpc", data=body,
                headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=60)
            assert e.value.code == 429
            assert int(e.value.headers["Retry-After"]) >= 1
            err = json.load(e.value)["error"]
            assert err["code"] == -32001
            assert err["data"]["retry_after_s"] >= 1.0
        finally:
            server.shutdown()

    def test_client_surfaces_retry_after(self):
        from spectre_tpu.prover_service.rpc_client import (ProverClient,
                                                           RpcError)
        server, port = self._overloaded_server()
        try:
            sleeps = []
            client = ProverClient(f"http://127.0.0.1:{port}/rpc",
                                  timeout=60, overload_retries=1,
                                  sleep=sleeps.append, rng=lambda: 0.0)
            params = _step_request_params(default_sync_step_args(TINY))
            with pytest.raises(RpcError) as e:
                client.submit_sync_step(
                    params["light_client_finality_update"],
                    params["pubkeys"], params["domain"])
            assert e.value.code == -32001
            assert e.value.retry_after is not None
            # the ONE bounded retry slept the server's hint before giving up
            assert len(sleeps) == 1
            assert sleeps[0] == pytest.approx(e.value.retry_after)
        finally:
            server.shutdown()

    def test_client_shedding_retry_then_success(self, monkeypatch):
        from spectre_tpu.prover_service.rpc import SERVICE_OVERLOADED
        from spectre_tpu.prover_service.rpc_client import (ProverClient,
                                                           RpcError)
        sleeps = []
        client = ProverClient("http://127.0.0.1:1/rpc", overload_retries=2,
                              retry_after_cap=30.0, sleep=sleeps.append,
                              rng=lambda: 0.0)
        calls = {"n": 0}

        def fake_call(method, params, timeout=None):
            calls["n"] += 1
            if calls["n"] < 3:
                raise RpcError(SERVICE_OVERLOADED, "service overloaded",
                               retry_after=2.5)
            return {"job_id": "j1"}

        monkeypatch.setattr(client, "_call", fake_call)
        assert client._call_shedding("m", {}) == {"job_id": "j1"}
        assert calls["n"] == 3
        assert sleeps == [2.5, 2.5]        # server hint honored, rng=0
        # an oversized hint is CAPPED (a shed must not park clients)
        sleeps.clear()
        calls["n"] = 0

        def fake_call_big(method, params, timeout=None):
            calls["n"] += 1
            if calls["n"] < 2:
                raise RpcError(SERVICE_OVERLOADED, "service overloaded",
                               retry_after=900.0)
            return {"job_id": "j2"}

        monkeypatch.setattr(client, "_call", fake_call_big)
        assert client._call_shedding("m", {}) == {"job_id": "j2"}
        assert sleeps == [30.0]

    def test_job_not_done_moved_to_32002(self):
        from spectre_tpu.prover_service.rpc import serve
        import threading
        done = threading.Event()     # the prove ends when the test says so
        server = serve(_FakeState(TINY, gate=lambda: done.wait(60)), port=0,
                       background=True)
        port = server.server_address[1]
        try:
            sub = _rpc_post(port, {
                "jsonrpc": "2.0", "id": 1,
                "method": "submitProof_SyncStepCompressed",
                "params": _step_request_params(
                    default_sync_step_args(TINY))}, timeout=60)["result"]
            err = _rpc_post(port, {"jsonrpc": "2.0", "id": 2,
                                   "method": "getProofResult",
                                   "params": {"job_id": sub["job_id"]}},
                            timeout=60)["error"]
            # -32001 now means "service overloaded"; pending moved here
            assert err["code"] == -32002
        finally:
            done.set()
            server.shutdown()

    def test_deadline_s_threads_through_rpc(self):
        from spectre_tpu.prover_service.rpc import serve
        server = serve(_FakeState(TINY, delay=1.0), port=0, background=True)
        port = server.server_address[1]
        try:
            params = _step_request_params(default_sync_step_args(TINY))
            params["deadline_s"] = 0.05
            jid = _rpc_post(port, {
                "jsonrpc": "2.0", "id": 1,
                "method": "submitProof_SyncStepCompressed",
                "params": params}, timeout=60)["result"]["job_id"]
            import time
            for _ in range(200):
                st = _rpc_post(port, {"jsonrpc": "2.0", "id": 2,
                                      "method": "getProofStatus",
                                      "params": {"job_id": jid}},
                               timeout=60)["result"]
                if st["status"] in ("done", "failed", "cancelled"):
                    break
                time.sleep(0.05)
            assert st["status"] == "failed"   # clamped by the client deadline
        finally:
            server.shutdown()


class TestCancelRace:
    """ISSUE 6 satellite: cancelProof racing completion must NOT resurrect
    a terminal job or delete its stored artifact."""

    def test_cancel_after_done_is_noop(self, tmp_path):
        import os
        from spectre_tpu.prover_service.jobs import JobQueue

        def runner(method, params):
            return {"proof": "0xfeed", "w": params["w"]}

        q = JobQueue(runner, concurrency=1, journal_dir=str(tmp_path))
        jid = q.submit("m", {"w": 1})
        job = q.wait(jid, timeout=10)
        assert job.status == "done"
        apath = q.store.path_for(job.result_digest)
        assert os.path.exists(apath)
        assert q.cancel(jid) is False        # terminal: cancel refused
        assert q.status(jid)["status"] == "done"
        assert q.result(jid).result == {"proof": "0xfeed", "w": 1}
        assert os.path.exists(apath)         # artifact untouched
        # restart still serves the result (journal unpolluted by the race)
        q.stop()
        q2 = JobQueue(runner, concurrency=1, journal_dir=str(tmp_path))
        assert q2.result(jid).result == {"proof": "0xfeed", "w": 1}
        q2.stop()

    def test_cancel_mid_run_still_cancels(self, tmp_path):
        from spectre_tpu.prover_service.jobs import JobQueue
        started = threading.Event()
        gate = threading.Event()

        def runner(method, params):
            started.set()
            gate.wait(timeout=30)
            return {"proof": "0xdead"}

        q = JobQueue(runner, concurrency=1, journal_dir=str(tmp_path))
        jid = q.submit("m", {"w": 2})
        assert started.wait(timeout=10)
        assert q.cancel(jid) is True
        gate.set()
        job = q.wait(jid, timeout=10)
        assert job.status == "cancelled"
        assert job.result is None            # late result discarded
        q.stop()


class TestCLI:
    def test_parser(self):
        from spectre_tpu.prover_service.cli import main
        with pytest.raises(SystemExit) as e:
            main(["--help"])
        assert e.value.code == 0
        with pytest.raises(SystemExit):
            main(["circuit", "bogus", "setup"])


class TestProfiling:
    def test_phase_timers(self):
        from spectre_tpu.observability import metrics, tracing
        from spectre_tpu.utils import profiling as prof
        hist = metrics.PHASE_SECONDS.labels(phase="unit/test")
        n0 = hist.snapshot()["count"]
        with tracing.trace("unit-phase-timers") as tr:
            with prof.phase("unit/test"):
                pass
        assert [c.name for c in tr.root.children] == ["unit/test"]
        assert tr.root.children[0].seconds() >= 0
        assert hist.snapshot()["count"] == n0 + 1
        assert not hasattr(prof, "totals")     # no registry beside the two


class TestEmittedSpectreSol:
    """The EMITTED Spectre.sol executes the same protocol flows as the
    Python model (reference: `contract-tests/tests/spectre.rs:34-110` runs
    the deployed contract with MockVerifiers; here the generated source is
    interpreted statement-by-statement)."""

    def _contract(self, period=2, poseidon=0x1234):
        from spectre_tpu.contracts.sol_gen import SolSpectre
        return SolSpectre(TINY, period, poseidon, MockVerifier(),
                          MockVerifier())

    def _step_input(self, **kw):
        d = dict(attested_slot=2 * TINY.slots_per_period + 5,
                 finalized_slot=2 * TINY.slots_per_period + 1,
                 participation=2,
                 finalized_header_root=b"\xAA" * 32,
                 execution_payload_root=b"\xBB" * 32)
        d.update(kw)
        return StepInput(**d)

    def test_sol_source_emitted(self, tmp_path):
        from spectre_tpu.contracts.sol_gen import gen_spectre_sol
        src = gen_spectre_sol(TINY)
        assert "contract Spectre" in src and "function step" in src
        p = tmp_path / "Spectre.sol"
        p.write_text(src)
        assert p.stat().st_size > 2000

    def test_step_advances_head_like_model(self):
        c = self._contract()
        inp = self._step_input()
        c.step(inp, b"")
        assert c.head == inp.finalized_slot
        assert c.storage["blockHeaderRoots"][inp.finalized_slot] == \
            int.from_bytes(inp.finalized_header_root, "big")
        # model comparison
        m = SpectreContract(spec=TINY, initial_sync_period=2,
                            initial_committee_poseidon=0x1234)
        m.step(inp, b"")
        assert m.head == c.head

    def test_commitment_matches_python_and_circuit_encoding(self):
        """Solidity toPublicInputsCommitment == StepInput model ==
        the circuit's instance encoding (`step_input_encoding.rs:109-116`)."""
        c = self._contract()
        inp = self._step_input()
        sin = {"attestedSlot": inp.attested_slot,
               "finalizedSlot": inp.finalized_slot,
               "participation": inp.participation,
               "finalizedHeaderRoot": int.from_bytes(
                   inp.finalized_header_root, "big"),
               "executionPayloadRoot": int.from_bytes(
                   inp.execution_payload_root, "big")}
        got = c.call("toPublicInputsCommitment", sin)
        assert got == inp.to_public_inputs_commitment()

    def test_step_rejects_low_participation(self):
        from spectre_tpu.contracts.sol_gen import SolRevert
        c = self._contract()
        inp = self._step_input(participation=1)
        with pytest.raises(SolRevert, match="insufficient participation"):
            c.step(inp, b"")

    def test_step_rejects_unknown_period(self):
        from spectre_tpu.contracts.sol_gen import SolRevert
        c = self._contract(period=0)
        with pytest.raises(SolRevert, match="no committee"):
            c.step(self._step_input(), b"")

    def test_rotate_flow_and_replay_protection(self):
        from spectre_tpu.contracts.sol_gen import SolRevert
        c = self._contract()
        inp = self._step_input()
        c.step(inp, b"")
        root = inp.finalized_header_root
        lo = int.from_bytes(root[16:], "big")
        hi = int.from_bytes(root[:16], "big")
        c.rotate(inp.finalized_slot, 0x777, lo, hi, b"")
        next_period = TINY.sync_period(inp.finalized_slot) + 1
        assert c.storage["syncCommitteePoseidons"][next_period] == 0x777
        with pytest.raises(SolRevert, match="already rotated"):
            c.rotate(inp.finalized_slot, 0x888, lo, hi, b"")
        with pytest.raises(SolRevert, match="header root mismatch"):
            c.rotate(inp.finalized_slot + 0, 0x999, lo + 1, hi, b"")

    def test_rejecting_verifier_blocks_step(self):
        from spectre_tpu.contracts.sol_gen import SolRevert, SolSpectre

        class Reject:
            def verify(self, instances, proof):
                return False

        c = SolSpectre(TINY, 2, 0x1234, Reject(), Reject())
        with pytest.raises(SolRevert, match="step proof invalid"):
            c.step(self._step_input(), b"")


class TestOutputIntegrityRPC:
    """ISSUE 9: the verify-before-serve layer as seen from the wire."""

    def test_healthz_gates_on_self_check(self):
        """A failing prove+verify self-check turns readiness into a 503
        with `self_check` in the body; a subsequent passing run restores
        200. The `health` RPC view carries the same snapshot."""
        import urllib.error

        from spectre_tpu.prover_service.rpc import serve
        from spectre_tpu.prover_service.selfverify import SelfCheck

        state = _FakeState(TINY)
        ok_box = {"ok": False}
        state.self_check = SelfCheck(runner=lambda: ok_box["ok"])
        state.self_check.run()
        server = serve(state, port=0, background=True)
        port = server.server_address[1]
        try:
            req = urllib.request.Request(f"http://127.0.0.1:{port}/healthz")
            with pytest.raises(urllib.error.HTTPError) as e:
                urllib.request.urlopen(req, timeout=60)
            assert e.value.code == 503
            body = json.load(e.value)
            assert body["status"] == "degraded"
            assert body["self_check"] == {"ok": False, "runs": 1,
                                          "last_error":
                                          "tiny-circuit proof failed "
                                          "verification"}
            ok_box["ok"] = True
            state.self_check.run()
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=60) as resp:
                data = json.load(resp)
            assert data["status"] == "ok"
            assert data["self_check"]["ok"] is True
            h = _rpc_post(port, {"jsonrpc": "2.0", "id": 1,
                                 "method": "health", "params": {}},
                          timeout=60)["result"]
            assert h["self_check"]["runs"] == 2
        finally:
            server.shutdown()

    def test_proof_verify_failed_sanitized_over_rpc(self):
        """A twice-failed self-verify surfaces as -32005 with the typed
        sanitized message — no traceback, no internals."""
        from spectre_tpu.prover_service.rpc import JOB_FAILED, serve
        from spectre_tpu.prover_service.selfverify import ProofVerifyFailed

        class _SdcState(_FakeState):
            def prove_step(self, args):
                raise ProofVerifyFailed("step")

        server = serve(_SdcState(TINY), port=0, background=True)
        port = server.server_address[1]
        try:
            args = default_sync_step_args(TINY)
            data = _rpc_post(port, {
                "jsonrpc": "2.0", "id": 1,
                "method": "genEvmProof_SyncStepCompressed",
                "params": _step_request_params(args)}, timeout=120)
            assert data["error"]["code"] == JOB_FAILED
            msg = data["error"]["message"]
            assert msg.startswith("proof failed self-verification")
            assert "quarantined" in msg
            assert "Traceback" not in msg and "File \"" not in msg
        finally:
            server.shutdown()

    def test_scrub_now_rpc(self, tmp_path):
        """scrubNow runs one scrubber pass over the queue's store and
        returns its summary; a hand-corrupted orphan is quarantined."""
        import os

        from spectre_tpu.prover_service.rpc import serve

        state = _FakeState(TINY)
        server = serve(state, port=0, background=True,
                       journal_dir=str(tmp_path), scrub_interval=0)
        port = server.server_address[1]
        try:
            store = state.jobs.store
            digest = store.write(b"rot me over rpc")
            path = store.path_for(digest)
            with open(path, "r+b") as f:
                f.seek(1)
                f.write(b"\xee")
            res = _rpc_post(port, {"jsonrpc": "2.0", "id": 1,
                                   "method": "scrubNow", "params": {}},
                            timeout=60)["result"]
            assert res["corrupt"] == 1
            assert res["scanned"] == 1
            assert not os.path.exists(path)
            assert os.path.exists(os.path.join(
                store.quarantine_dir, os.path.basename(path)))
        finally:
            state.jobs.stop()
            server.shutdown()
