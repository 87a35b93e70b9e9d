#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the served prove path runs on the chip.

    python chip_smoke.py                      one chip, Minimal-32, k=14
    python chip_smoke.py --chips 4            the 2x2 mesh prove and its references
    python chip_smoke.py --spec testnet --k 18

Default invocation (one chip, ONE process — the server, the HTTP client and
the native CPU reference all live here; a chip belongs to one process):

  1. refuse to run unless `jax.devices()[0].platform == "tpu"`;
  2. rebuild the native host library from `src/spectre_host.cc` (it is
     compiled `-march=native`; a copy built elsewhere can SIGILL here) and
     point PARAMS_DIR/BUILD_DIR at a fresh work directory, so nothing that
     runs was built by another code version;
  3. `ProverState(spec, k, k, backend="tpu")` -> `rpc.serve(port=0)` and,
     over real HTTP through `ProverClient`: /healthz ready, two blocking
     `genEvmProof_CommitteeUpdateCompressed` requests and one
     `submitProof_...` + `getProofResult`, each for a distinct seeded
     committee update. Every proof went through verify-before-serve; the
     second blocking request's manifest must show `compile.count == 0`;
  4. outside the requests: one seeded-blinding prove on `TpuBackend` and one
     on `CpuBackend` for the same witness — the bytes must be equal;
  5. every fallback/degrade counter must still be zero.

`--chips 4` runs only the mesh path and what it is compared with: the same
witness proved directly (no server) on the 2x2 ("data","win") mesh with the
three shard gates lowered to 2^12, again under SPECTRE_MESH_SHAPE=1x1, and
on `CpuBackend`; all three proofs byte-equal, no degrade, every device used.

The last stdout line is `{"ok": true, "device": {...}}`; any failure exits
non-zero and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke_work")      # git-ignored, wiped per run
T0 = time.time()

# a served prove that leaned on any of these did not prove on the device
ZERO_COUNTERS = (
    "prove_cpu_fallbacks_oom", "prove_cpu_fallbacks_compile",
    "proofs_sdc_retried", "proofs_verify_failed", "msm_fixed_degraded",
    "quotient_sharded_degraded", "self_check_failures",
)
SHARD_GATES = ("SPECTRE_SHARD_MSM_MIN_LOGN", "SPECTRE_SHARD_NTT_MIN_LOGN",
               "SPECTRE_SHARD_QUOTIENT_MIN_LOGN")


class SmokeFailure(Exception):
    pass


def log(msg: str):
    print(f"[chip_smoke {time.time() - T0:7.1f}s] {msg}", flush=True)


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(chips: int) -> dict:
    """The device as JAX reports it — or a failure that names what JAX
    found instead. Runs before anything else touches the program."""
    try:
        import jax
        devs = jax.devices()
    except Exception as exc:
        raise SmokeFailure(f"JAX found no usable device "
                           f"({type(exc).__name__}: {exc})")
    platform = devs[0].platform
    check(platform == "tpu",
          f"JAX is running on platform {platform!r} "
          f"({devs[0].device_kind}, {len(devs)} device(s)), not a TPU")
    check(len(devs) == chips,
          f"asked for {chips} chip(s), JAX reports {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def prepare(spec_name: str, k: int):
    """Native library rebuilt from tracked source; fresh params/build dirs
    (BUILD_DIR/PARAMS_DIR are read when spectre_tpu is first imported)."""
    native = os.path.join(HERE, "spectre_tpu", "native")
    check(os.path.isdir(native),
          f"{native} not found: chip_smoke.py runs from the root of a "
          f"spectre-tpu checkout")
    r = subprocess.run(["make", "-B", "-C", native], capture_output=True,
                       text=True)
    check(r.returncode == 0,
          f"native host library build failed:\n{r.stdout}{r.stderr}")
    shutil.rmtree(WORK, ignore_errors=True)
    os.environ["BUILD_DIR"] = os.path.join(WORK, "build")
    os.environ["PARAMS_DIR"] = os.path.join(WORK, "params")
    os.makedirs(os.environ["BUILD_DIR"])
    os.makedirs(os.environ["PARAMS_DIR"])
    # a tracked pinning (the production column shape) rides along; a pk
    # pickle never does
    pin = os.path.join(HERE, "build",
                       f"committee_update_{spec_name}_{k}.pinning.json")
    if os.path.exists(pin):
        shutil.copy(pin, os.environ["BUILD_DIR"])
    log(f"native library rebuilt; work dir {WORK}")


def committee_update(spec, seed: int) -> dict:
    """A valid `light_client_update` for `spec` from a seed (distinct
    committee per seed), in the JSON shape `preprocessor/rotation.py`
    parses. The branch is built at pubkeys depth, so no aggregate-pubkey
    extension applies."""
    from spectre_tpu.witness.rotation import default_committee_update_args
    args = default_committee_update_args(spec, seed=seed)
    h = args.finalized_header
    pubkeys = ["0x" + pk.hex() for pk in args.pubkeys_compressed]
    return {
        "finalized_header": {
            "slot": h.slot, "proposer_index": h.proposer_index,
            "parent_root": "0x" + h.parent_root.hex(),
            "state_root": "0x" + h.state_root.hex(),
            "body_root": "0x" + h.body_root.hex()},
        "next_sync_committee": {"pubkeys": pubkeys,
                                "aggregate_pubkey": pubkeys[0]},
        "next_sync_committee_branch":
            ["0x" + b.hex() for b in args.sync_committee_branch],
    }


def counters() -> dict:
    from spectre_tpu.utils.health import HEALTH
    return HEALTH.snapshot()["counters"]


def check_zero_counters():
    snap = counters()
    bad = {c: snap[c] for c in ZERO_COUNTERS if snap.get(c, 0)}
    check(not bad, f"fallback/degrade counters ticked: {bad}")
    log("fallback/degrade counters all zero: " + ", ".join(ZERO_COUNTERS))


def peak_hbm() -> list:
    import jax
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def seeded_prove(pk, srs, asg, bk, seed: int) -> bytes:
    from spectre_tpu.plonk.prover import prove
    from spectre_tpu.test_utils import seeded_blinding_rng
    return prove(pk, srs, asg, bk, blinding_rng=seeded_blinding_rng(seed))


def prove_on_both(pk, srs, asg, device_bk, seed: int, tag: str):
    """(device proof, CpuBackend proof) of one witness under the same seeded
    blinding. The native reference runs on a thread beside the device prove
    (ctypes and device waits release the GIL): neither is a timing that
    anything reads, and the smoke has a wall-clock limit."""
    from concurrent.futures import ThreadPoolExecutor

    from spectre_tpu.plonk import backend as B
    with ThreadPoolExecutor(max_workers=1) as ex:
        t = time.time()
        ref = ex.submit(seeded_prove, pk, srs, asg, B.get_backend("cpu"), seed)
        p_dev = seeded_prove(pk, srs, asg, device_bk, seed)
        t_dev = time.time() - t
        p_cpu = ref.result()
    log(f"seeded prove, side by side: {tag} {t_dev:.1f}s, CpuBackend done "
        f"after {time.time() - t:.1f}s, {len(p_dev)} bytes")
    return p_dev, p_cpu


def log_manifest(tag: str, man: dict):
    comp = man["compile"]
    log(f"{tag}: prove_s={man['prove_s']} queue_wait_s={man['queue_wait_s']} "
        f"compile.count={comp['count']} compile.seconds={comp['seconds']}")
    phases = {p: round(s, 3) for p, s in man["phase_seconds"].items()
              if p.startswith(("prove/", "job/", "state/"))}
    log(f"{tag}: phase_seconds={json.dumps(phases)}")


def run_served(spec, k: int, seed: int):
    """Phase 3-5 of the default invocation. Returns nothing; raises
    SmokeFailure on the first broken expectation."""
    from spectre_tpu.models import CommitteeUpdateCircuit
    from spectre_tpu.plonk import backend as B
    from spectre_tpu.preprocessor.rotation import rotation_args_from_update
    from spectre_tpu.prover_service import rpc
    from spectre_tpu.prover_service.rpc_client import ProverClient
    from spectre_tpu.prover_service.state import ProverState

    params_dir = os.environ["PARAMS_DIR"]
    t = time.time()
    state = ProverState(spec, k, k, concurrency=1, backend="tpu",
                        params_dir=params_dir, compress=False)
    log(f"ProverState up in {time.time() - t:.1f}s (SRS 2^{k}, self-check; "
        f"no circuit keygen yet)")
    # the first request pays keygen + every compile with no heartbeat in
    # between: keep the stall supervisor off it
    server = rpc.serve(state, port=0, background=True, stall_timeout=3000.0,
                       scrub_interval=0)
    try:
        url = f"http://127.0.0.1:{server.server_address[1]}"
        with urllib.request.urlopen(url + "/healthz", timeout=60) as resp:
            health = json.load(resp)
            check(resp.status == 200 and health["status"] == "ok",
                  f"/healthz not ready: {health}")
        log(f"serving on {url}; /healthz ok "
            f"(self_check={health['self_check']})")

        client = ProverClient(url, timeout=3000.0)
        before = counters()
        updates = [committee_update(spec, seed + 1000 * i) for i in range(3)]
        job_ids = []
        for i in (0, 1):
            t = time.time()
            res = client.gen_evm_proof_committee_update_compressed(updates[i])
            dt = time.time() - t
            # the blocking call hides its job id; the same witness dedups
            # onto the finished job
            jid = client.submit_committee_update(updates[i])
            job_ids.append(jid)
            log(f"blocking request {i + 1}: {dt:.1f}s, proof "
                f"{(len(res['proof']) - 2) // 2} bytes, job {jid}")
            log_manifest(f"blocking request {i + 1}", client.get_manifest(jid))
        t = time.time()
        jid = client.submit_committee_update(updates[2])
        check(jid not in job_ids, "async submit was deduplicated")
        res = client.wait_for_proof(jid, poll=0.5, timeout=3000.0)
        check(res == client.proof_result(jid), "getProofResult disagrees")
        log(f"async request (submitProof + getProofResult): "
            f"{time.time() - t:.1f}s, proof "
            f"{(len(res['proof']) - 2) // 2} bytes, job {jid}")
        log_manifest("async request", client.get_manifest(jid))

        after = counters()
        verified = after.get("proofs_verified", 0) \
            - before.get("proofs_verified", 0)
        check(verified == 3,
              f"verify-before-serve ran {verified} times for 3 proofs")
        log("proofs_verified rose by 3 (verify-before-serve, host verifier)")
        warm = client.get_manifest(job_ids[1])["compile"]["count"]
        check(warm == 0,
              f"second blocking request compiled {warm} program(s)")

        # byte check, outside the requests: same witness, same seeded
        # blinding, the server's own pk and SRS, both backends
        args = rotation_args_from_update(updates[0], spec)
        ctx = CommitteeUpdateCircuit.build_context(args, spec)
        pk, srs = state.committee_pk, state.srs[k]
        asg = ctx.assignment(pk.vk.config)
        p_dev, p_cpu = prove_on_both(pk, srs, asg, state.backend, seed,
                                     "TpuBackend")
        check(p_dev == p_cpu, "TpuBackend proof bytes != CpuBackend proof "
                              "bytes under the same seeded blinding")
        log("TpuBackend proof is byte-equal to the CpuBackend proof")
        check_zero_counters()
        log(f"peak_bytes_in_use per device: {peak_hbm()}")
    finally:
        server.shutdown()
        server.server_close()
        if state.jobs is not None:
            state.jobs.stop()


def run_four_chips(spec, k: int, seed: int):
    """The mesh prove and what it is compared with — no server."""
    from spectre_tpu.models import CommitteeUpdateCircuit
    from spectre_tpu.plonk import backend as B
    from spectre_tpu.plonk.srs import SRS
    from spectre_tpu.witness.rotation import default_committee_update_args

    cpu = B.get_backend("cpu")
    srs = SRS.load_or_setup(k, os.environ["PARAMS_DIR"])
    args = default_committee_update_args(spec, seed=seed)
    pk = CommitteeUpdateCircuit.create_pk(srs, spec, k, args, cpu)
    ctx = CommitteeUpdateCircuit.build_context(args, spec)
    asg = ctx.assignment(pk.vk.config)
    for gate in SHARD_GATES:                 # the existing gates, lowered
        os.environ[gate] = "12"
    os.environ.pop("SPECTRE_MESH_SHAPE", None)
    mesh_bk = B.TpuBackend()
    check(mesh_bk._use_mesh(1 << k, mesh_bk._shard_ntt_min_logn),
          "mesh gates not engaged: this would prove on one device")
    from spectre_tpu.parallel.plan import current_plan
    plan = current_plan()
    log(f"mesh {dict(plan.mesh.shape)} over {plan.n_devices} devices")
    check(dict(plan.mesh.shape) == {"data": 2, "win": 2},
          f"expected the 2x2 (data, win) mesh, got {dict(plan.mesh.shape)}")
    p_mesh, p_cpu = prove_on_both(pk, srs, asg, mesh_bk, seed,
                                  "2x2 mesh (compiles included)")
    check(p_mesh == p_cpu, "2x2 mesh proof bytes != CpuBackend proof bytes")
    placed = [hit[1] for hit in mesh_bk._mesh_base_cache.values()]
    check(placed and all(len(a.sharding.device_set) == 4 for a in placed),
          "the commitment base was not placed over all four devices")
    peaks = peak_hbm()
    log(f"peak_bytes_in_use per device after the mesh prove: {peaks}")
    # (XLA:CPU reports no memory stats: there the placement check stands alone)
    check(len(peaks) == 4 and all(p is None or p > 0 for p in peaks),
          f"a device held nothing during the mesh prove: {peaks}")

    os.environ["SPECTRE_MESH_SHAPE"] = "1x1"
    one_bk = B.TpuBackend()
    check(not one_bk._use_mesh(1 << k, 0), "1x1 still routed to the mesh")
    t = time.time()
    p_one = seeded_prove(pk, srs, asg, one_bk, seed)
    log(f"1x1 prove: {time.time() - t:.1f}s (compiles included)")
    check(p_one == p_cpu, "1x1 proof bytes != CpuBackend proof bytes")
    log("2x2 mesh, 1x1 and CpuBackend proofs are byte-equal")
    check_zero_counters()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--spec", default="minimal",
                    help="spec preset (minimal = 32 validators)")
    ap.add_argument("--k", type=int, default=14)
    ap.add_argument("--seed", type=int, default=42)
    opts = ap.parse_args(argv)
    try:
        device = require_tpu(opts.chips)
        log(f"device: {json.dumps(device)}")
        prepare(opts.spec, opts.k)
        from spectre_tpu import spec as spec_mod
        check(opts.spec in spec_mod.SPECS, f"unknown spec {opts.spec!r}")
        spec = spec_mod.SPECS[opts.spec]
        log(f"committee-update, spec {spec.name} "
            f"({spec.sync_committee_size} validators), k={opts.k}, "
            f"seed {opts.seed}")
        if opts.chips == 4:
            run_four_chips(spec, opts.k, opts.seed)
        else:
            run_served(spec, opts.k, opts.seed)
    except SmokeFailure as exc:
        print(f"chip_smoke: FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    log(f"total {time.time() - T0:.1f}s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
