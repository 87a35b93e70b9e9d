"""ProverState: SRS loaded at boot, each circuit's proving key on first use.

Reference parity: `prover/src/prover.rs:43-117` (`ProverState::new`: SRS map
by degree, pkeys for step/committee circuits created from default witnesses)
and the semaphore-based concurrency cap (`prover.rs:40`) — here a
threading.Semaphore, acquired by the RPC handlers.

PR 3: every prove routes through `backend.prove_with_fallback` — a device
OOM / Mosaic compile failure retries once on the CPU backend instead of
failing the request — and `params_dir` additionally hosts the async job
journal (`jobs.ensure_jobs` attaches the queue lazily at serve time).

PR 25: a circuit's proving key is built (or loaded from the pk cache) by
the first request that needs it, not at construction — a committee-only
server no longer pays the step circuit's witness build + keygen (minutes
even at Minimal) before it answers anything. `state.step_pk` /
`state.committee_pk` / `state.*_agg_pk` read the same as before.
"""

from __future__ import annotations

import threading

from ..models import CommitteeUpdateCircuit, StepCircuit
from ..plonk import backend as B
from ..plonk.srs import SRS
from ..utils.profiling import phase
from ..witness import default_committee_update_args, default_sync_step_args


def _lazy_pk(name: str):
    """Proving key `name`, built once by the first reader (under a lock:
    concurrent first requests must not keygen the same circuit twice)."""
    def get(self):
        with self._pk_lock:
            if name not in self._pks:
                with phase(f"state/create_pk_{name}"):
                    self._pks[name] = self._pk_builders[name]()
            return self._pks[name]
    return property(get)


class ProverState:
    step_pk = _lazy_pk("step")
    committee_pk = _lazy_pk("committee")
    step_agg_pk = _lazy_pk("step_agg")
    committee_agg_pk = _lazy_pk("committee_agg")

    def __init__(self, spec, k_step: int, k_committee: int,
                 concurrency: int = 1, backend: str = "cpu",
                 params_dir: str | None = None, compress: bool = False,
                 k_agg: int = 17):
        """compress: run the full two-stage flow (app snark with Poseidon
        transcript -> in-circuit verification in the aggregation circuit ->
        Keccak-transcript outer proof), the reference's `*Compressed` RPC
        semantics. Boot additionally creates the two aggregation pkeys from
        dummy app snarks (`cli.rs:241-280`'s dummy-proof-at-setup)."""
        # compile telemetry (ISSUE 8): register the jax.monitoring
        # listener BEFORE any jit fires, so pk-creation/boot compiles are
        # counted too — after boot, a prove whose manifest shows
        # compile.count == 0 provably hit the jit caches
        from ..observability import compilelog
        compilelog.install()
        self.spec = spec
        self.backend = B.get_backend(backend)
        self.concurrency = concurrency
        self.semaphore = threading.Semaphore(concurrency)
        self.params_dir = params_dir      # also hosts the async job journal
        self.jobs = None                  # attached lazily (jobs.ensure_jobs)
        self.srs = {}
        for k in {k_step, k_committee}:
            self.srs[k] = SRS.load_or_setup(k, params_dir)
        self.k_step, self.k_committee = k_step, k_committee
        self._pk_lock = threading.RLock()   # agg builders read the app pk
        self._pks: dict = {}
        self._pk_builders = {
            "step": lambda: StepCircuit.create_pk(
                self.srs[k_step], spec, k_step,
                default_sync_step_args(spec), self.backend),
            "committee": lambda: CommitteeUpdateCircuit.create_pk(
                self.srs[k_committee], spec, k_committee,
                default_committee_update_args(spec), self.backend),
        }
        self.compress = compress
        if compress:
            from ..models import AggregationCircuit
            self.k_agg = k_agg
            self.srs[k_agg] = SRS.load_or_setup(k_agg, params_dir)
            self.step_agg = AggregationCircuit.variant("sync_step")
            self.committee_agg = AggregationCircuit.variant("committee_update")
            # the dummy inner proof is a thunk: only generated when the
            # aggregation pk is not already cached on disk
            self._pk_builders["step_agg"] = lambda: self.step_agg.create_pk(
                self.srs[k_agg], spec, k_agg,
                lambda: self._dummy_agg_args(StepCircuit, self.step_pk,
                                             self.k_step,
                                             default_sync_step_args(spec)),
                self.backend)
            self._pk_builders["committee_agg"] = \
                lambda: self.committee_agg.create_pk(
                    self.srs[k_agg], spec, k_agg,
                    lambda: self._dummy_agg_args(
                        CommitteeUpdateCircuit, self.committee_pk,
                        self.k_committee,
                        default_committee_update_args(spec)),
                    self.backend)
        # readiness self-check (ISSUE 9): prove+verify a tiny cached
        # circuit before the box reports ready — GET /healthz stays 503
        # until it passes, and it re-runs after every SDC retry
        from .selfverify import SelfCheck
        self.self_check = SelfCheck()
        with phase("boot/self_check"):
            self.self_check.run()

    def _dummy_agg_args(self, circuit, pk, k, dummy_args):
        from ..models import AggregationArgs
        from ..plonk.transcript import PoseidonTranscript
        proof = circuit.prove(pk, self.srs[k], dummy_args, self.spec,
                              self.backend, transcript=PoseidonTranscript())
        inst = circuit.get_instances(dummy_args, self.spec)
        return AggregationArgs(inner_vk=pk.vk, srs=self.srs[k],
                               inner_instances=[inst], proof=proof)

    def _compressed(self, circuit, pk, k, agg_cls, agg_pk, args, bk=None,
                    heartbeat=None):
        from ..models import AggregationArgs, AggregationCircuit
        from ..plonk.transcript import KeccakTranscript, PoseidonTranscript
        hb = heartbeat or (lambda: None)
        bk = bk if bk is not None else self.backend
        with phase("prove/app_snark"):
            app_proof = circuit.prove(pk, self.srs[k], args, self.spec, bk,
                                      transcript=PoseidonTranscript())
        hb()              # phase boundary: app snark done, aggregation next
        inst = circuit.get_instances(args, self.spec)
        agg_args = AggregationArgs(inner_vk=pk.vk, srs=self.srs[k],
                                   inner_instances=[inst], proof=app_proof)
        with phase("prove/aggregation"):
            outer = agg_cls.prove(agg_pk, self.srs[self.k_agg], agg_args,
                                  self.spec, bk,
                                  transcript=KeccakTranscript())
        hb()
        return outer, AggregationCircuit.get_instances(agg_args, self.spec)

    def _release_idle_ext_caches(self, *active_pks):
        """Drop cached extended-domain fixed columns on every pk EXCEPT the
        ones about to prove: the per-pk caches are GBs at production degrees
        and would otherwise stack across circuit families (all four pks
        resident), raising the service's peak RSS well above one prove's."""
        for pk in list(self._pks.values()):    # built keys only
            if all(pk is not a for a in active_pks):
                pk.release_ext_cache()

    def prove_step(self, args, heartbeat=None,
                   backend=None) -> tuple[bytes, list]:
        """`heartbeat` (optional zero-arg callback, threaded in by the job
        queue's worker) is stamped between prove phases so the supervisor
        can tell a long legitimate prove from a hung worker. `backend`
        overrides the boot backend for this one prove — the self-verify
        SDC retry pins it to CPU (selfverify.verified_prove)."""
        hb = heartbeat or (lambda: None)
        bk0 = backend if backend is not None else self.backend
        with self.semaphore:
            hb()                     # phase: permit acquired, prove starts
            self._release_idle_ext_caches(
                self.step_pk, self.step_agg_pk if self.compress else None)
            if self.compress:
                return B.prove_with_fallback(
                    lambda bk: self._compressed(StepCircuit, self.step_pk,
                                                self.k_step, self.step_agg,
                                                self.step_agg_pk, args,
                                                bk=bk, heartbeat=hb),
                    bk0)
            proof = B.prove_with_fallback(
                lambda bk: StepCircuit.prove(self.step_pk,
                                             self.srs[self.k_step],
                                             args, self.spec, bk),
                bk0)
            hb()
        return proof, StepCircuit.get_instances(args, self.spec)

    def prove_step_batch(self, args_list: list) -> list:
        """Prove a batch of sync-step requests concurrently (SURVEY §2c(b)):
        a pool sized by the concurrency governor; each worker still takes a
        semaphore permit, so combined RPC + batch load honors one cap.
        Witness generation runs in threads (builder work releases the GIL
        during backend/numpy calls); commit-phase MSMs of concurrent proofs
        share the backend's cached device base and the mesh batch axis."""
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=max(1, self.concurrency)) as ex:
            return list(ex.map(self.prove_step, args_list))

    def prove_committee_batch(self, args_list: list) -> list:
        from concurrent.futures import ThreadPoolExecutor

        with ThreadPoolExecutor(max_workers=max(1, self.concurrency)) as ex:
            return list(ex.map(self.prove_committee, args_list))

    def prove_committee(self, args, heartbeat=None,
                        backend=None) -> tuple[bytes, list]:
        hb = heartbeat or (lambda: None)
        bk0 = backend if backend is not None else self.backend
        with self.semaphore:
            hb()
            self._release_idle_ext_caches(
                self.committee_pk,
                self.committee_agg_pk if self.compress else None)
            if self.compress:
                return B.prove_with_fallback(
                    lambda bk: self._compressed(CommitteeUpdateCircuit,
                                                self.committee_pk,
                                                self.k_committee,
                                                self.committee_agg,
                                                self.committee_agg_pk, args,
                                                bk=bk, heartbeat=hb),
                    bk0)
            proof = B.prove_with_fallback(
                lambda bk: CommitteeUpdateCircuit.prove(
                    self.committee_pk, self.srs[self.k_committee], args,
                    self.spec, bk),
                bk0)
            hb()
        return proof, CommitteeUpdateCircuit.get_instances(args, self.spec)

    def verify_proof(self, kind: str, proof: bytes, instances: list) -> bool:
        """Host-side check of a fresh proof against the matching verifying
        key — the half second verify-before-serve spends so an SDC'd
        prove never leaves the box (selfverify.verified_prove). `kind` is
        "step" or "committee"; `instances` is the flat public-input list
        the prove returned."""
        if self.compress:
            from ..plonk.transcript import KeccakTranscript
            agg = self.step_agg if kind == "step" else self.committee_agg
            agg_pk = (self.step_agg_pk if kind == "step"
                      else self.committee_agg_pk)
            return bool(agg.verify(agg_pk.vk, self.srs[self.k_agg],
                                   instances, proof,
                                   transcript_cls=KeccakTranscript))
        circuit = StepCircuit if kind == "step" else CommitteeUpdateCircuit
        pk = self.step_pk if kind == "step" else self.committee_pk
        k = self.k_step if kind == "step" else self.k_committee
        return bool(circuit.verify(pk.vk, self.srs[k], instances, proof))
