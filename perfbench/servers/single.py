"""One prover service in the run's own process: `ProverState` behind
`rpc.serve`, driven over real HTTP. From the program this takes only the
system under test and what it exposes (manifests, span trees, health
counters, the verifying key it serves proofs against).

A later deployment shape (replicas behind the dispatcher, say) is another
file in this directory, named by the configuration's `server` key."""

from __future__ import annotations

import json
import urllib.request

# a served prove that leaned on any of these did not prove on the device
ZERO_COUNTERS = (
    "prove_cpu_fallbacks_oom", "prove_cpu_fallbacks_compile",
    "proofs_sdc_retried", "proofs_verify_failed", "msm_fixed_degraded",
    "quotient_sharded_degraded", "self_check_failures",
)


class Served:
    zero_counters = ZERO_COUNTERS

    def __init__(self, config: dict, traffic: dict, paths: dict):
        from spectre_tpu import spec as spec_mod
        from spectre_tpu.prover_service import rpc
        from spectre_tpu.prover_service.state import ProverState

        self.config = config
        spec = spec_mod.SPECS[config["spec"]]
        k = int(config["k"])
        self.state = ProverState(
            spec, k, k, concurrency=int(traffic.get("concurrency", 1)),
            backend=config["backend"], params_dir=paths["params"],
            compress=bool(config.get("compress", False)))
        # the first request pays keygen and every compile with no heartbeat
        # in between: keep the stall supervisor off it
        self.server = rpc.serve(self.state, port=0, background=True,
                                journal_dir=paths["journal"],
                                stall_timeout=3000.0, scrub_interval=0)
        self.url = f"http://127.0.0.1:{self.server.server_address[1]}"
        with urllib.request.urlopen(self.url + "/healthz", timeout=60) as r:
            health = json.load(r)
            if r.status != 200 or health["status"] != "ok":
                raise RuntimeError(f"/healthz not ready: {health}")

    def client(self):
        from spectre_tpu.prover_service.rpc_client import ProverClient
        return ProverClient(self.url, timeout=3000.0)

    def counters(self) -> dict:
        from spectre_tpu.utils.health import HEALTH
        return HEALTH.snapshot()["counters"]

    def backend(self):
        return self.state.backend

    def verifying_key(self) -> dict:
        """The verifying key the service checks its proofs against, as plain
        numbers (shape + commitments as affine integer pairs or None). The
        reference hashes it itself and holds it to the configuration's
        pinned digest before it trusts a point of it."""
        pk = getattr(self.state, self.config["proving_key"] + "_pk")
        vk, cfg = pk.vk, pk.vk.config

        def pts(lst):
            return [None if p is None else (int(p[0]), int(p[1]))
                    for p in (lst or [])]

        return {
            "shape": {"k": cfg.k, "num_advice": cfg.num_advice,
                      "num_lookup_advice": cfg.num_lookup_advice,
                      "num_fixed": cfg.num_fixed,
                      "lookup_bits": cfg.lookup_bits,
                      "num_instance": cfg.num_instance,
                      "lookup_tables": list(cfg.lookup_tables),
                      "num_sha_slots": cfg.num_sha_slots},
            "selector_commits": pts(vk.selector_commits),
            "fixed_commits": pts(vk.fixed_commits),
            "sigma_commits": pts(vk.sigma_commits),
            "table_commits": pts(vk.table_commits),
            "sha_selector_commits": pts(vk.sha_selector_commits),
            "sha_k_commit": (pts([vk.sha_k_commit])[0]
                             if cfg.num_sha_slots else None),
        }

    def close(self):
        if self.server is None:
            return
        self.server.shutdown()
        self.server.server_close()
        if self.state.jobs is not None:
            self.state.jobs.stop()
        self.server = None


def boot(config: dict, traffic: dict, paths: dict) -> Served:
    return Served(config, traffic, paths)
