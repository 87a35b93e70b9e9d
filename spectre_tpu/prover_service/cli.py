"""spectre-tpu prover CLI.

Reference parity: `prover/src/args.rs:32-170` + `cli.rs:35-242`:
  circuit {sync-step,committee-update} setup   -- SRS + pk generation
  circuit ... prove                            -- prove a witness file
  rpc                                          -- serve the JSON-RPC API
  utils committee-poseidon                     -- deployment bootstrap values
plus `--backend {cpu,tpu}` (the BASELINE.json north-star selection point) and
`--spec {minimal,testnet,mainnet}` network dispatch (`main.rs:27-57`).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .. import spec as spec_mod


def _spec(name):
    return spec_mod.SPECS[name]


def _require_tpu():
    """`--backend tpu` means the chip. TpuBackend itself runs on whatever
    platform JAX has (the tests drive it on XLA:CPU through
    `get_backend("tpu")`), so the operator's entry point is where a
    missing accelerator must stop the program: proving on XLA:CPU under
    the name "tpu" is a wrong answer to the question the flag asks."""
    try:
        import jax
        platform = jax.devices()[0].platform
    except Exception as exc:       # backend failed to initialise at all
        raise SystemExit(f"--backend tpu: JAX found no usable device "
                         f"({type(exc).__name__}: {exc})")
    if platform != "tpu":
        raise SystemExit(f"--backend tpu: JAX is running on platform "
                         f"{platform!r}, not a TPU; use --backend cpu for "
                         f"the native host prover")


def main(argv=None):
    p = argparse.ArgumentParser(prog="spectre-tpu")
    p.add_argument("--spec", default="minimal", choices=list(spec_mod.SPECS))  # incl. "tiny" demo net
    p.add_argument("--backend", default="cpu", choices=["cpu", "tpu"])
    sub = p.add_subparsers(dest="cmd", required=True)

    c = sub.add_parser("circuit", help="circuit lifecycle")
    c.add_argument("which", choices=["sync-step", "committee-update",
                                     "sync-step-compressed",
                                     "committee-update-compressed"])
    c.add_argument("action", choices=["setup", "prove", "verify",
                                      "gen-verifier"])
    c.add_argument("--k", type=int, default=17)
    c.add_argument("--k-agg", type=int, default=17,
                   help="aggregation circuit degree (compressed variants)")
    c.add_argument("--witness", help="witness JSON path (default: mock witness)")
    c.add_argument("--proof-out", default="proof.bin")
    c.add_argument("--proof-in")
    c.add_argument("--sol-out", help="Solidity output path "
                   "(default: build/<name>_<spec>_<k>_verifier.sol)")

    r = sub.add_parser("rpc", help="serve JSON-RPC prover API")
    r.add_argument("--host", default="127.0.0.1")
    r.add_argument("--port", type=int, default=3000)
    r.add_argument("--k-step", type=int, default=17)
    r.add_argument("--k-committee", type=int, default=17)
    r.add_argument("--concurrency", type=int, default=1)
    r.add_argument("--compress", action="store_true",
                   help="serve two-stage (aggregated) EVM proofs")
    r.add_argument("--k-agg", type=int, default=17)
    r.add_argument("--params-dir", help="SRS/pk cache dir; also hosts the "
                   "crash-safe async job journal (jobs.journal.jsonl)")
    r.add_argument("--job-timeout", type=float, default=None,
                   help="default per-job deadline in seconds for async "
                   "submitProof_* jobs (default: none)")
    r.add_argument("--queue-depth", type=int, default=None,
                   help="admission-control backlog bound; a full queue "
                   "sheds submits with -32001/429 + Retry-After "
                   "(default: $SPECTRE_JOB_QUEUE_DEPTH or 64)")
    r.add_argument("--mem-watermark-mb", type=float, default=None,
                   help="shed NEW submissions once RSS exceeds this "
                   "(default: $SPECTRE_MEM_WATERMARK_MB; 0 disables)")
    r.add_argument("--worker-stall-s", type=float, default=None,
                   help="supervisor stall threshold: a worker whose "
                   "heartbeat is older than this is replaced and its job "
                   "failed (default: $SPECTRE_WORKER_STALL_S or 600)")
    r.add_argument("--replicas", default=None,
                   help="comma-separated prover replica URLs (default "
                        "$SPECTRE_REPLICAS): serve as a proof-farm "
                        "dispatcher over them instead of proving "
                        "locally (ISSUE 11)")
    r.add_argument("--replica-id", default=None,
                   help="this server's replica id within a farm "
                        "(default $SPECTRE_REPLICA_ID); stamped into "
                        "RPC errors and proof manifests")
    r.add_argument("--lease-s", type=float, default=None,
                   help="dispatcher lease duration in seconds (default "
                        "$SPECTRE_REPLICA_LEASE_S or 120): a replica "
                        "owns a job only while its heartbeat renews "
                        "within this window")
    r.add_argument("--announce-to", default=None,
                   help="dispatcher head URL to announce this replica "
                        "to (default $SPECTRE_ANNOUNCE_URL): joins the "
                        "proof farm dynamically via registerReplica "
                        "with a capability record + heartbeat (ISSUE 18)")
    r.add_argument("--announce-interval", type=float, default=None,
                   help="seconds between announce heartbeats (default "
                        "$SPECTRE_ANNOUNCE_INTERVAL_S or 15)")
    r.add_argument("--advertise-url", default=None,
                   help="URL the dispatcher should dial back (default "
                        "http://<host>:<port> of this server — set when "
                        "behind NAT/a proxy)")
    r.add_argument("--ttl-s", type=float, default=None,
                   help="dispatcher-side heartbeat TTL for dynamic "
                        "members (default $SPECTRE_REPLICA_TTL_S or "
                        "60): a silent replica is demoted through its "
                        "breaker and deregistered after this long")
    r.add_argument("--trace-dir", default=None,
                   help="write each completed job's span tree as Chrome "
                   "trace-event JSON (<job_id>.trace.json) under this "
                   "directory (default: $SPECTRE_TRACE_DIR; unset "
                   "disables the file sink — getTrace still serves the "
                   "in-memory ring)")

    f = sub.add_parser("follow", help="run the light-client follower: "
                       "track the beacon head, prove steps + committee "
                       "updates, serve verified updates over the RPC API")
    f.add_argument("--beacon-api", required=True,
                   help="Beacon REST base URL; pass a comma-separated "
                        "list to poll a quorum (2-of-N agreement on the "
                        "finalized head; a lone dissenting beacon is "
                        "demoted behind its breaker)")
    f.add_argument("--beacon-quorum", type=int, default=None,
                   help="matching finalized heads required before the "
                        "follower acts (default $SPECTRE_BEACON_QUORUM "
                        "or 2, clamped to the pool size)")
    f.add_argument("--params-dir", required=True,
                   help="SRS/pk cache dir; hosts the job journal AND the "
                   "follower's verified update store "
                   "(follower.updates.jsonl + results/)")
    f.add_argument("--host", default="127.0.0.1")
    f.add_argument("--port", type=int, default=3000)
    f.add_argument("--poll-s", type=float, default=None,
                   help="beacon poll cadence (default: "
                   "$SPECTRE_FOLLOW_POLL_S or 12)")
    f.add_argument("--backfill", type=int, default=None,
                   help="max committee-update periods queued per poll "
                   "(default: $SPECTRE_FOLLOW_BACKFILL or 8)")
    f.add_argument("--domain", default=None,
                   help="sync-committee signing domain (hex); step proofs "
                   "are disabled without it")
    f.add_argument("--pubkeys-file", default=None,
                   help="JSON list of compressed pubkey hex strings for "
                   "the current committee; step proofs are disabled "
                   "without it")
    f.add_argument("--k-step", type=int, default=17)
    f.add_argument("--k-committee", type=int, default=17)
    f.add_argument("--k-agg", type=int, default=17)
    f.add_argument("--concurrency", type=int, default=1)
    f.add_argument("--compress", action="store_true",
                   help="prove two-stage (aggregated) EVM proofs")
    f.add_argument("--job-timeout", type=float, default=None)
    f.add_argument("--queue-depth", type=int, default=None)
    f.add_argument("--gateway", action="store_true",
                   help="mount the cacheable GET /v1/* read plane "
                        "(ISSUE 14): content-addressed ETags, 304s, "
                        "immutable cache headers on sealed periods, "
                        "pre-built update-range packs")
    f.add_argument("--pack-periods", type=int, default=None,
                   help="periods per sealed update pack (default "
                        "$SPECTRE_PACK_PERIODS or 8)")
    f.add_argument("--agg-cadence", type=int, default=None,
                   help="publish an EVM-verifiable aggregation proof "
                        "every N sealed committee periods (default "
                        "$SPECTRE_AGG_CADENCE_PERIODS or 0 = off)")
    f.add_argument("--gateway-cache-mb", type=float, default=None,
                   help="gateway hot-cache byte budget in MB (default "
                        "$SPECTRE_GATEWAY_CACHE_MB or 64)")

    u = sub.add_parser("utils", help="deployment utilities")
    u.add_argument("util", choices=["committee-poseidon"])
    u.add_argument("--beacon-api", help="Beacon REST base URL")

    fl = sub.add_parser("faults", help="fault-injection site registry")
    fl.add_argument("--list", action="store_true",
                    help="print the site table (markdown, the source of "
                    "the README fault-sites section)")
    fl.add_argument("--json", action="store_true",
                    help="machine-readable sites + kinds")

    s = sub.add_parser("scrub", help="offline artifact scrub: re-hash every "
                       "results/ file against its content address, "
                       "quarantine rot, expire journal orphans")
    s.add_argument("--params-dir", required=True,
                   help="the dir hosting the job journal + results/ store")
    s.add_argument("--min-age-s", type=float, default=0.0,
                   help="only expire orphans older than this (default 0: "
                   "the service is assumed stopped, everything is fair "
                   "game; the in-service scrubber defaults to "
                   "$SPECTRE_SCRUB_MIN_AGE_S or 60)")

    args = p.parse_args(argv)
    spec = _spec(args.spec)
    if args.backend == "tpu":
        _require_tpu()

    if args.cmd == "circuit":
        _circuit_cmd(args, spec)
    elif args.cmd == "rpc":
        from .rpc import serve
        from .state import ProverState
        # compile telemetry before the first jit: boot/pk-creation
        # compiles land in spectre_compile_seconds and per-job manifests
        # (render one with `python -m spectre_tpu.observability report`)
        from ..observability import compilelog
        compilelog.install()
        print(f"loading prover state (spec={spec.name}, backend={args.backend})...",
              flush=True)
        state = ProverState(spec, args.k_step, args.k_committee,
                            args.concurrency, args.backend,
                            params_dir=args.params_dir,
                            compress=args.compress, k_agg=args.k_agg)
        print(f"serving on {args.host}:{args.port} "
              f"(async jobs journaled under "
              f"{args.params_dir or 'params_dir unset: in-memory only'})",
              flush=True)
        if args.trace_dir is not None:
            from ..observability.tracing import TRACE_DIR_ENV
            os.environ[TRACE_DIR_ENV] = args.trace_dir
        queue_kw = {}
        if args.queue_depth is not None:
            queue_kw["queue_depth"] = args.queue_depth
        if args.mem_watermark_mb is not None:
            queue_kw["mem_watermark_mb"] = args.mem_watermark_mb
        if args.worker_stall_s is not None:
            queue_kw["stall_timeout"] = args.worker_stall_s
        dispatcher = None
        replicas_raw = args.replicas or os.environ.get("SPECTRE_REPLICAS")
        if replicas_raw:
            # proof farm (ISSUE 11): this process becomes the dispatcher
            # head — jobs route to the replica fleet; the local state
            # only cross-verifies what the replicas return
            from .dispatcher import Dispatcher, HttpReplica
            from .rpc_client import ProverClient
            urls = [u.strip() for u in replicas_raw.split(",") if u.strip()]
            dispatcher = Dispatcher(
                replicas=[HttpReplica(url, ProverClient(url))
                          for url in urls],
                journal_dir=args.params_dir, lease_s=args.lease_s,
                ttl_s=args.ttl_s, verify_state=state)
            print(f"dispatching over {len(urls)} replicas "
                  f"(lease {dispatcher.lease_s:g}s, heartbeat TTL "
                  f"{dispatcher.ttl_s:g}s, cross-verify on)",
                  flush=True)
        elif args.ttl_s is not None:
            # dispatcher head with an EMPTY static fleet (ISSUE 18):
            # every replica joins dynamically via registerReplica
            from .dispatcher import Dispatcher
            dispatcher = Dispatcher(replicas=[],
                                    journal_dir=args.params_dir,
                                    lease_s=args.lease_s,
                                    ttl_s=args.ttl_s, verify_state=state)
            print(f"dispatching over announce-only fleet (heartbeat TTL "
                  f"{dispatcher.ttl_s:g}s)", flush=True)
        serve(state, args.host, args.port, job_timeout=args.job_timeout,
              dispatcher=dispatcher, replica_id=args.replica_id,
              announce=args.announce_to,
              announce_interval=args.announce_interval,
              advertise_url=args.advertise_url,
              **queue_kw)
    elif args.cmd == "utils":
        _utils_cmd(args, spec)
    elif args.cmd == "follow":
        _follow_cmd(args, spec)
    elif args.cmd == "faults":
        _faults_cmd(args)
    elif args.cmd == "scrub":
        _scrub_cmd(args)


def _follow_cmd(args, spec):
    """Supervised follower daemon (ISSUE 10): beacon head tracking +
    proof scheduling in the foreground, the RPC serving API (including
    getLightClientUpdate) in the background on the same process."""
    import threading

    from ..follower import Follower
    from ..observability import compilelog
    from ..preprocessor.beacon import BeaconClient, BeaconQuorum
    from .jobs import ensure_jobs
    from .rpc import serve
    from .state import ProverState

    compilelog.install()
    pubkeys = None
    if args.pubkeys_file:
        with open(args.pubkeys_file) as fh:
            pubkeys = json.load(fh)
    domain = args.domain
    if not (pubkeys and domain):
        print("step proofs disabled (need both --pubkeys-file and "
              "--domain); following committee updates only", flush=True)
    print(f"loading prover state (spec={spec.name}, "
          f"backend={args.backend})...", flush=True)
    state = ProverState(spec, args.k_step, args.k_committee,
                        args.concurrency, args.backend,
                        params_dir=args.params_dir,
                        compress=args.compress, k_agg=args.k_agg)
    queue_kw = {}
    if args.queue_depth is not None:
        queue_kw["queue_depth"] = args.queue_depth
    jobs = ensure_jobs(state, journal_dir=args.params_dir,
                       default_timeout=args.job_timeout, **queue_kw)
    beacon_urls = [u.strip() for u in args.beacon_api.split(",")
                   if u.strip()]
    if len(beacon_urls) > 1:
        # multi-beacon quorum (ISSUE 11 satellite): the follower acts
        # only on a finalized head 2-of-N beacons agree on; a lone
        # lying/forked beacon is demoted behind its own breaker
        beacon = BeaconQuorum([BeaconClient(u) for u in beacon_urls],
                              quorum=args.beacon_quorum)
        print(f"beacon quorum: {beacon.quorum}-of-{len(beacon_urls)}",
              flush=True)
    else:
        beacon = BeaconClient(beacon_urls[0])
    publisher = None
    if args.agg_cadence:
        # aggregation cadence (ISSUE 18): publish through the Spectre
        # contract reference model — swap in an EvmProofVerifier-backed
        # contract to gate publishes on the generated Solidity verifier
        from ..contracts.spectre import SpectreContract
        from ..follower.scheduler import AggregationPublisher
        contract = SpectreContract(spec, 0, 0)
        publisher = AggregationPublisher(contract)
        print(f"aggregation cadence: every {args.agg_cadence} sealed "
              f"periods", flush=True)
    fol = Follower(spec, beacon, jobs, directory=args.params_dir,
                   pubkeys=pubkeys, domain=domain, backfill=args.backfill,
                   cadence_periods=args.agg_cadence, publisher=publisher)
    gateway = None
    if args.gateway:
        from ..gateway import Gateway
        gateway = Gateway(fol.store, pack_periods=args.pack_periods,
                          cache_mb=args.gateway_cache_mb)
        print(f"gateway mounted on /v1/* (pack_periods="
              f"{gateway.packs.pack_periods}, cache "
              f"{gateway.cache.budget >> 20} MB)", flush=True)
    serve(state, args.host, args.port, background=True,
          journal_dir=args.params_dir, job_timeout=args.job_timeout,
          follower=fol, gateway=gateway, **queue_kw)
    print(f"following {args.beacon_api}; serving light-client updates "
          f"on {args.host}:{args.port}", flush=True)
    stop = threading.Event()
    try:
        fol.run(stop, poll_s=args.poll_s)
    except KeyboardInterrupt:
        stop.set()


def _faults_cmd(args):
    """Print the fault-site registry (ISSUE 10 satellite): `--list` is
    the markdown table the README embeds verbatim; `--json` the raw
    registry for tooling."""
    from ..utils import faults
    if args.json:
        print(json.dumps({"sites": {k: {"module": m, "injects": d}
                                    for k, (m, d) in faults.SITES.items()},
                          "kinds": list(faults.KINDS)}, indent=2))
    else:
        print(faults.render_site_table())


def _scrub_cmd(args):
    """One offline scrubber pass (ISSUE 9): replay the journal to learn
    which digests are live, then re-hash/quarantine/expire the store."""
    from ..observability.manifest import MANIFEST_SUFFIX
    from ..utils.artifacts import ArtifactStore
    from .jobs import JobJournal
    from .scrubber import Scrubber

    jobs = JobJournal(args.params_dir).replay()
    live = set()
    for job in jobs.values():
        if job.result_digest is not None:
            live.add((job.result_digest, ".bin"))
        if job.manifest_digest is not None:
            live.add((job.manifest_digest, MANIFEST_SUFFIX))
    # a follower params dir keeps its verified updates (and the
    # gateway's update-range packs) in the SAME artifact store — replay
    # those journals too, or an offline pass expires the whole chain
    from ..follower.updates import JOURNAL_NAME, UpdateStore
    if os.path.exists(os.path.join(args.params_dir, JOURNAL_NAME)):
        from ..gateway.packs import PackBuilder
        ustore = UpdateStore(args.params_dir)
        live |= ustore.live_artifacts()
        live |= PackBuilder(ustore).live_artifacts()
    store = ArtifactStore(args.params_dir)
    summary = Scrubber(store, lambda: live,
                       min_age_s=args.min_age_s).scrub()
    summary["live"] = len(live)
    print(json.dumps(summary))


def _circuit_cmd(args, spec):
    from ..models import CommitteeUpdateCircuit, StepCircuit
    from ..plonk import backend as B
    from ..plonk.srs import SRS
    from ..witness import default_committee_update_args, default_sync_step_args

    compressed = args.which.endswith("-compressed")
    base = args.which.removesuffix("-compressed")
    circuit = StepCircuit if base == "sync-step" else CommitteeUpdateCircuit
    default_args = (default_sync_step_args if base == "sync-step"
                    else default_committee_update_args)(spec)
    bk = B.get_backend(args.backend)
    srs = SRS.load_or_setup(args.k)

    if args.action == "setup" and not compressed:
        pk = circuit.create_pk(srs, spec, args.k, default_args, bk)
        print(f"pk ready: {circuit.pinning_path(spec, args.k)}")
        return

    witness_args = default_args
    if args.witness:
        with open(args.witness) as f:
            data = json.load(f)
        witness_args = _witness_from_json(base, data)

    pk = circuit.create_pk(srs, spec, args.k, default_args, bk)

    if compressed:
        _compressed_circuit_cmd(args, spec, circuit, pk, srs,
                                default_args, witness_args, bk)
        return

    if args.action == "gen-verifier":
        # reference: `spectre-prover circuit ... gen-verifier`
        # (`util/circuit.rs:182-194`)
        from ..evm import gen_evm_verifier
        from ..models.app_circuit import BUILD_DIR
        n_inst = len(circuit.get_instances(default_args, spec))
        src = gen_evm_verifier(pk.vk, srs, num_instances=n_inst,
                               contract_name=f"Verifier_{circuit.name}")
        out = args.sol_out or os.path.join(
            BUILD_DIR, f"{circuit.name}_{spec.name}_{args.k}_verifier.sol")
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            f.write(src)
        print(json.dumps({"verifier": out, "bytes": len(src)}))
        return
    if args.action == "prove":
        proof = circuit.prove(pk, srs, witness_args, spec, bk)
        with open(args.proof_out, "wb") as f:
            f.write(proof)
        instances = circuit.get_instances(witness_args, spec)
        print(json.dumps({"proof": args.proof_out, "bytes": len(proof),
                          "instances": [hex(v) for v in instances]}))
    elif args.action == "verify":
        with open(args.proof_in or args.proof_out, "rb") as f:
            proof = f.read()
        instances = circuit.get_instances(witness_args, spec)
        ok = circuit.verify(pk.vk, srs, instances, proof)
        print(json.dumps({"valid": bool(ok)}))
        sys.exit(0 if ok else 1)


def _compressed_circuit_cmd(args, spec, circuit, pk, srs, default_args,
                            witness_args, bk):
    """Two-stage lifecycle (reference: `sync-step-compressed` CLI paths):
    app snark (Poseidon transcript) -> aggregation circuit -> outer proof
    (Keccak for the EVM calldata path)."""
    from ..models import AggregationArgs, AggregationCircuit
    from ..plonk.srs import SRS
    from ..plonk.transcript import KeccakTranscript, PoseidonTranscript

    agg_cls = AggregationCircuit.variant(circuit.name)
    srs_agg = SRS.load_or_setup(args.k_agg)

    def agg_args_for(wargs):
        proof = circuit.prove(pk, srs, wargs, spec, bk,
                              transcript=PoseidonTranscript())
        inst = circuit.get_instances(wargs, spec)
        return AggregationArgs(inner_vk=pk.vk, srs=srs,
                               inner_instances=[inst], proof=proof)

    agg_pk = agg_cls.create_pk(srs_agg, spec, args.k_agg,
                               lambda: agg_args_for(default_args), bk)
    if args.action == "setup":
        print(f"pk ready: {agg_cls.pinning_path(spec, args.k_agg)}")
        return
    if args.action == "gen-verifier":
        from ..evm import gen_evm_verifier
        from ..models.app_circuit import BUILD_DIR
        # statement = 12 accumulator limbs + the app instances (no proving
        # needed to size it)
        n_inst = 12 + len(circuit.get_instances(default_args, spec))
        src = gen_evm_verifier(agg_pk.vk, srs_agg, num_instances=n_inst,
                               contract_name=f"Verifier_{agg_cls.name}",
                               num_acc_limbs=12)
        out = args.sol_out or os.path.join(
            BUILD_DIR, f"{agg_cls.name}_{spec.name}_{args.k_agg}_verifier.sol")
        os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
        with open(out, "w") as f:
            f.write(src)
        print(json.dumps({"verifier": out, "bytes": len(src)}))
        return
    inst_path = args.proof_out + ".instances.json"
    if args.action == "prove":
        agg_args = agg_args_for(witness_args)
        proof = agg_cls.prove(agg_pk, srs_agg, agg_args, spec, bk,
                              transcript=KeccakTranscript())
        instances = AggregationCircuit.get_instances(agg_args, spec)
        with open(args.proof_out, "wb") as f:
            f.write(proof)
        # the statement binds the (blinded, non-reproducible) app proof:
        # persist it next to the outer proof for later verification
        with open(inst_path, "w") as f:
            json.dump({"instances": [hex(v) for v in instances]}, f)
        print(json.dumps({"proof": args.proof_out, "bytes": len(proof),
                          "instances": inst_path}))
    elif args.action == "verify":
        with open(args.proof_in or args.proof_out, "rb") as f:
            proof = f.read()
        src_path = ((args.proof_in or args.proof_out)
                    + ".instances.json")
        with open(src_path) as f:
            instances = [int(v, 16) for v in json.load(f)["instances"]]
        ok = agg_cls.verify(agg_pk.vk, srs_agg, instances, proof,
                            transcript_cls=KeccakTranscript)
        print(json.dumps({"valid": bool(ok)}))
        sys.exit(0 if ok else 1)


def _witness_from_json(which: str, data: dict):
    from ..preprocessor.rotation import rotation_args_from_update
    from ..preprocessor.step import step_args_from_finality_update
    if which == "sync-step":
        raise SystemExit("sync-step witness JSON requires the update+pubkeys "
                         "format; use the rpc API or the preprocessor directly")
    return rotation_args_from_update(data, _spec(data.get("spec", "minimal")))


def _utils_cmd(args, spec):
    from ..fields import bls12_381 as bls
    from ..gadgets.poseidon_commit import committee_poseidon_from_uncompressed
    from .beacon_helpers import fetch_bootstrap_committee

    assert args.util == "committee-poseidon"
    assert args.beacon_api, "--beacon-api required"
    period, root, pubkeys = fetch_bootstrap_committee(args.beacon_api, spec)
    pts = [bls.g1_decompress(pk) for pk in pubkeys]
    commitment = committee_poseidon_from_uncompressed(pts)
    print(json.dumps({
        "sync_period": period,
        "committee_ssz_root": "0x" + root.hex(),
        "committee_poseidon": hex(commitment),
    }))


if __name__ == "__main__":
    main()
