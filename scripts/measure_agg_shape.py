#!/usr/bin/env python
"""Measure aggregation-circuit pinnings across outer degrees (VERDICT r4
item 8): the reference compresses with K=23 / 1 advice / lookup 19
(`config/sync_step_verifier_23.json`); this repo's r4 flagship used
k_agg=21 / 12 advice. Fewer columns = fewer witness commitments = smaller
outer proof and cheaper calldata/verifier; fewer rows = faster prove. This
script builds the aggregation context over the CURRENT flagship inner proof
and records the column counts + estimated proof bytes for each k, so the
trade is adopted or rejected with numbers.

Run after the step pipeline's stage 1:
    JAX_PLATFORMS=cpu \
        python scripts/measure_agg_shape.py [--spec testnet] [--k 21]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def estimate_proof_bytes(cfg) -> int:
    """Outer proof size from the config alone: one G1 (64 B uncompressed in
    our wire format: 2x32) per commitment, 32 B per evaluation, plus the two
    SHPLONK witness points. Commitments: advice + per-lookup (pA, pT, z) +
    permutation z chunks + 3 quotient chunks. Evals follow the query plan:
    ~1 per advice/fixed/selector/sigma/table column-rotation pair; the
    dominant, config-derivable part is counted, transcript tails ignored."""
    commitments = (cfg.num_advice + 3 * cfg.num_lookup_advice
                   + cfg.num_perm_chunks + 3)
    evals = (cfg.num_advice * 4              # gate rotations 0..3
             + cfg.num_fixed + cfg.num_advice      # fixed + selectors
             + cfg.num_perm_columns                # sigmas
             + 3 * cfg.num_lookup_advice * 2       # pA/pT/tab + z pairs
             + 2 * cfg.num_perm_chunks)
    return 64 * commitments + 32 * evals + 2 * 64


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec", default="testnet")
    ap.add_argument("--k", type=int, default=21)
    ap.add_argument("--out", default=None)
    opts = ap.parse_args()

    import jax
    jax.config.update("jax_platforms", "cpu")
    from spectre_tpu.plonk.backend import setup_compile_cache
    setup_compile_cache()
    from spectre_tpu import spec as S
    from spectre_tpu.models import AggregationArgs, AggregationCircuit
    from spectre_tpu.models.app_circuit import BUILD_DIR
    from spectre_tpu.models.step import StepCircuit
    from spectre_tpu.plonk.srs import SRS
    from spectre_tpu.witness.step import default_sync_step_args

    spec = S.SPECS[opts.spec]
    k = opts.k
    proof_path = os.path.join(BUILD_DIR,
                              f"step_{spec.name}_{k}_poseidon.proof")
    assert os.path.exists(proof_path), \
        f"{proof_path} missing — run the step pipeline's stage 1 first"
    with open(proof_path, "rb") as f:
        proof = f.read()

    srs = SRS.load_or_setup(k)
    args = default_sync_step_args(spec)
    pk = StepCircuit.create_pk(srs, spec, k, args)   # cached pk load
    inst = StepCircuit.get_instances(args, spec)
    agg_cls = AggregationCircuit.variant(StepCircuit.name)
    agg_args = AggregationArgs(inner_vk=pk.vk, srs=srs,
                               inner_instances=[inst], proof=proof)
    t = time.time()
    ctx = agg_cls.build_context(agg_args, spec)
    cells = ctx.stats()["advice_cells"]
    print(f"agg context: {cells:,} advice cells ({time.time()-t:.0f}s build)")

    rows = []
    for k_agg in range(21, 26):
        try:
            cfg = ctx.auto_config(k=k_agg,
                                  lookup_bits=agg_cls.default_lookup_bits)
        except AssertionError as e:
            print(f"k={k_agg}: {e}")
            continue
        est = estimate_proof_bytes(cfg)
        rows.append({
            "k_agg": k_agg, "num_advice": cfg.num_advice,
            "num_lookup_advice": cfg.num_lookup_advice,
            "est_proof_bytes": est,
            # prove cost scales ~ (columns+const) * n*log n for NTT/MSM work
            "relative_ntt_msm_cost": round(
                (cfg.num_advice + 3 * cfg.num_lookup_advice + 8)
                * (1 << k_agg) * k_agg
                / ((12 + 6 + 8) * (1 << 21) * 21), 2),
        })
        print(f"k={k_agg}: advice={cfg.num_advice} "
              f"lookup={cfg.num_lookup_advice} est_proof={est} B "
              f"rel_cost={rows[-1]['relative_ntt_msm_cost']}")

    out_path = opts.out or os.path.join(BUILD_DIR,
                                        f"agg_shape_{spec.name}_{k}.json")
    with open(out_path, "w") as f:
        json.dump({"inner_proof_bytes": len(proof),
                   "agg_advice_cells": cells, "shapes": rows}, f, indent=1)
    print("wrote", out_path)


if __name__ == "__main__":
    main()
