#!/usr/bin/env python3
"""The control of `correct`, at a cell's own size, on the machine that holds
the chip (the benchmark's own runs do not run it):

    python3 perfbench/tests/control_on_chip.py --workload cu-minimal32.serial --seeds 11,12,13

One service, one warm-up prove, then for each seed a short window at the
cell's own load through the window's own entry (`--seconds`, by default one
more than the last client's stagger: each client of the mix sends once, so
one proof a seed in `serial` and in `pair` two, the second 13 s after the
first and beside it from there). Each served result is judged by the reference as a run
judges it (has to pass), then once with each guarantee broken (has to be
refused): a commitment, an evaluation and the opening altered, another
request's header, another committee, another set-up, a truncated proof, and
the result served for another seed's request. Prints one line per reading and
exits 0 only if every sound one passed and every control was refused."""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(1, os.path.dirname(os.path.dirname(HERE)))

import run as bench  # noqa: E402
from harness import cells, device, loadgen, workdir  # noqa: E402
from reference import bn254_g1 as g1, plonk  # noqa: E402


def controls(ref, request, result, other_request):
    proof = bytes.fromhex(result["proof"][2:])
    inst = [int(v, 16) for v in result["instances"]]
    n_commit = plonk.commitment_plan(ref.vk.shape)[3]

    def flipped(pos):
        bad = bytearray(proof)
        bad[pos] ^= 1
        return dict(result, proof="0x" + bytes(bad).hex())

    def with_inst(values):
        return dict(result, instances=[hex(v) for v in values],
                    committee_poseidon=hex(values[ref.offset]))

    w2 = g1.from_bytes(proof[-64:])
    yield "commitment_altered", request, flipped(64 * 3 + 40)
    yield "evaluation_altered", request, flipped(64 * n_commit + 32 * 5 + 31)
    yield "opening_altered", request, dict(
        result, proof="0x" + (proof[:-64]
                              + g1.to_bytes(g1.add(w2, g1.G))).hex())
    yield "other_committee", request, with_inst(
        [(inst[0] + 1) % g1.R] + inst[1:])
    yield "truncated", request, dict(result, proof="0x" + proof[:-32].hex())
    yield "served_for_another_request", other_request, result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    opts = ap.parse_args()
    seeds = [int(s) for s in opts.seeds.split(",")]
    cell = cells.load_cell(opts.workload)
    config, traffic = cell.config, cell.traffic
    if opts.seconds is None:
        opts.seconds = 1.0 + traffic.get("stagger_s", 0.0) \
            * (traffic["clients"] - 1)
    device.require_platform(config["platform"], cell.chips)
    paths = workdir.prepare(config)
    requests = cells.load_plugin("requests", config["circuit"])
    reference = cells.load_plugin("reference", config["circuit"])
    served = cells.load_plugin("servers", config.get("server", "single")) \
        .boot(config, traffic, paths)
    bad = 0
    try:
        client = served.client()
        client._call(requests.METHOD,
                     requests.make(config, seeds[0], "warmup")["params"])
        results = []
        for seed in seeds:
            w = loadgen.Window(served, traffic,
                               lambda i, s=seed: requests.make(config, s, i),
                               (requests.METHOD, requests.SUBMIT_METHOD),
                               opts.seconds)
            w.run()
            results += [(f"{seed}/{s.index}", s) for s in w.sent]
            bench.log(f"seed {seed}: {len(w.sent)} served in {w.wall_s:.1f}s "
                      f"errors={[s.error for s in w.sent if s.error]}")
        ref = reference.Reference(config, served.verifying_key())
        for i, (seed, sent) in enumerate(results):
            if sent.error:
                print(f"seed {seed} sound FAILED {sent.error}")
                bad += 1
                continue
            why = ref.check(sent.request, sent.result)
            print(f"seed {seed} sound {'passes' if not why else 'REFUSED: ' + why}")
            bad += bool(why)
            other = results[(i + 1) % len(results)][1].request
            for name, req, res in controls(ref, sent.request, sent.result,
                                           other):
                why = ref.check(req, res)
                print(f"seed {seed} control {name}: "
                      f"{'refused: ' + why if why else 'PASSED (a fault)'}")
                bad += not why
            wrong_tau = plonk.verify(
                ref.vk, ref.tau + 1,
                [[int(v, 16) for v in sent.result["instances"]]],
                bytes.fromhex(sent.result["proof"][2:]))
            print(f"seed {seed} control other_setup: "
                  f"{'refused: ' + wrong_tau if wrong_tau else 'PASSED (a fault)'}")
            bad += not wrong_tau
    finally:
        served.close()
    print(f"control_on_chip: {'ok' if not bad else str(bad) + ' fault(s)'}")
    sys.stdout.flush()
    os._exit(1 if bad else 0)


if __name__ == "__main__":
    main()
