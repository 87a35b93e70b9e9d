"""Aggregation/compression layer: in-circuit SHPLONK verification.

Reference parity: `aggregation_circuit.rs` + snark-verifier's
`AggregationCircuit` (mock + proof tests mirror
`sync_step_circuit.rs:544-604`'s two-stage flow at framework scale).

Default tier: the full in-circuit verification of a REAL inner proof
(witness generation + accumulator parity + the deferred pairing), and the
reject paths. RUN_SLOW tier: constraint satisfaction of the whole verifier
circuit and the outer prove/verify round-trip.
"""

import os
import random

import pytest

from spectre_tpu.builder.context import Context
from spectre_tpu.builder.range_chip import RangeChip
from spectre_tpu.fields import bn254
from spectre_tpu.models.aggregation import (Accumulator, AggregationArgs,
                                            AggregationCircuit, accumulate)
from spectre_tpu.plonk.in_circuit import VerifierChip
from spectre_tpu.plonk.keygen import keygen
from spectre_tpu.plonk.mock import mock_prove
from spectre_tpu.plonk.prover import prove
from spectre_tpu.plonk.srs import SRS
from spectre_tpu.plonk.transcript import PoseidonTranscript

RUN_SLOW = os.environ.get("RUN_SLOW") == "1"
R = bn254.R
P = bn254.P


@pytest.fixture(scope="module")
def inner():
    """A small app circuit proven with the Poseidon transcript."""
    random.seed(3)
    ctx = Context()
    rng = RangeChip(lookup_bits=8)
    g = rng.gate
    a = ctx.load_witness(1234)
    b = ctx.load_witness(5678)
    c = g.mul(ctx, a, b)
    rng.range_check(ctx, a, 16)
    ctx.expose_public(c)
    cfg = ctx.auto_config(k=10, lookup_bits=8)
    asg = ctx.assignment(cfg)
    srs = SRS.unsafe_setup(10)
    pk = keygen(srs, cfg, asg.fixed, asg.selectors, asg.copies)
    proof = prove(pk, srs, asg, transcript=PoseidonTranscript())
    return pk, srs, asg.instances, proof


def _host_msm(vc):
    """`MsmChip.msm`'s answer from the host curve: the sum of the same
    (point, scalar) terms, loaded as the cells the chip would return."""
    g1 = bn254.g1_curve

    def msm(ctx, witness_pairs, constant_pairs):
        acc = None
        for (x, y), s in witness_pairs:
            pt = (bn254.Fq(x.value % P), bn254.Fq(y.value % P))
            acc = g1.add(acc, g1.mul(pt, s.value % R))
        for pt, s in constant_pairs:
            acc = g1.add(acc, g1.mul(pt, s.value % R))
        return (vc.fq.load(ctx, int(acc[0])), vc.fq.load(ctx, int(acc[1])))

    return msm


class TestAccumulator:
    def test_limbs_roundtrip(self):
        g1 = bn254.g1_curve
        acc = Accumulator(lhs=g1.mul(bn254.G1_GEN, 7),
                          rhs=g1.mul(bn254.G1_GEN, 11))
        back = Accumulator.from_limbs(acc.limbs())
        assert (int(back.lhs[0]), int(back.lhs[1])) == \
            (int(acc.lhs[0]), int(acc.lhs[1]))
        assert (int(back.rhs[0]), int(back.rhs[1])) == \
            (int(acc.rhs[0]), int(acc.rhs[1]))

    def test_accumulate_is_deterministic_fiat_shamir(self):
        g1 = bn254.g1_curve
        accs = [Accumulator(g1.mul(bn254.G1_GEN, i + 2),
                            g1.mul(bn254.G1_GEN, i + 9)) for i in range(3)]
        a1 = accumulate(accs)
        a2 = accumulate(accs)
        assert (int(a1.lhs[0]), int(a1.rhs[0])) == \
            (int(a2.lhs[0]), int(a2.rhs[0]))
        # different input order -> different challenges
        a3 = accumulate(list(reversed(accs)))
        assert int(a3.lhs[0]) != int(a1.lhs[0])


class TestNativeAccumulator:
    def test_valid_proof_accumulates_and_pairs(self, inner):
        pk, srs, instances, proof = inner
        acc = VerifierChip.native_accumulator(pk.vk, srs, instances, proof)
        assert acc is not None
        assert acc.check(srs)

    def test_identity_failure_returns_none(self, inner):
        pk, srs, instances, proof = inner
        bad = [[(instances[0][0] + 1) % R]]
        assert VerifierChip.native_accumulator(pk.vk, srs, bad, proof) is None

    def test_tampered_commitment_fails_pairing(self, inner):
        pk, srs, instances, proof = inner
        # flip a byte in the FIRST commitment (point section): the identity
        # check at x still passes only with negligible probability; either
        # outcome (None or failed pairing) must reject
        bad = bytearray(proof)
        bad[1] ^= 1
        try:
            acc = VerifierChip.native_accumulator(pk.vk, srs, instances,
                                                  bytes(bad))
        except AssertionError:
            return  # off-curve / non-canonical: rejected at parse
        assert acc is None or not acc.check(srs)


class TestInCircuitVerifier:
    def test_accumulator_matches_native(self, inner):
        """The flagship path: a real proof verified as constraints; the
        cell-level accumulator equals the native one and the deferred
        pairing closes."""
        pk, srs, instances, proof = inner
        acc_native = VerifierChip.native_accumulator(pk.vk, srs, instances,
                                                     proof)
        ctx = Context()
        rng = RangeChip(lookup_bits=14)
        vc = VerifierChip(rng)
        cells = [[ctx.load_witness(int(v)) for v in col] for col in instances]
        lhs, rhs = vc.verify_proof(ctx, pk.vk, srs, cells, proof)
        assert (lhs[0].value % P, lhs[1].value % P) == \
            (int(acc_native.lhs[0]), int(acc_native.lhs[1]))
        assert (rhs[0].value % P, rhs[1].value % P) == \
            (int(acc_native.rhs[0]), int(acc_native.rhs[1]))
        assert Accumulator(
            lhs=(bn254.Fq(lhs[0].value % P), bn254.Fq(lhs[1].value % P)),
            rhs=(bn254.Fq(rhs[0].value % P), bn254.Fq(rhs[1].value % P)),
        ).check(srs)

    @pytest.mark.parametrize("msm", [
        "host_msm", pytest.param("msm_chip", marks=pytest.mark.slow)])
    def test_sha_region_inner_proof_aggregates(self, msm, monkeypatch):
        """An inner proof whose circuit uses the wide-SHA region (extra
        commitment/query-plan keys: shb/shw/shq/shk) must flow through the
        in-circuit verifier and close the deferred pairing.

        The wide region is some 190 commitments whatever the message, and
        the in-circuit MSM witnesses each as 64 windows of non-native
        additions in Python: minutes, all of it the loop that
        `test_accumulator_matches_native` runs over the small proof's
        terms. `host_msm` hands the verifier's terms (every commitment key
        with its in-circuit scalar) to the host curve and loads the sum, so
        the keys, the transcript, the query plan and the scalars are what
        is checked; `msm_chip` is the whole of it, in the slow tier."""
        from spectre_tpu.builder import GateChip
        from spectre_tpu.builder.sha256_wide_chip import Sha256WideChip
        from spectre_tpu.gadgets import ssz_merkle as M

        ctx = Context()
        sha = Sha256WideChip(GateChip())
        cells = M.load_bytes_checked(ctx, sha, b"agg over wide sha")
        digest = sha.digest_bytes(ctx, cells)
        ctx.expose_public(digest[0].cell)
        cfg = ctx.auto_config(k=9, lookup_bits=5)
        asg = ctx.assignment(cfg)
        srs = SRS.unsafe_setup(11)
        pk = keygen(srs, cfg, asg.fixed, asg.selectors, asg.copies)
        proof = prove(pk, srs, asg, transcript=PoseidonTranscript())
        assert {k[0] for k in pk.vk.commitment_plan()[0]} \
            >= {"shb", "shw"} and {k[0] for k, _ in pk.vk.query_plan()} \
            >= {"shb", "shw", "shq", "shk"}

        acc = VerifierChip.native_accumulator(pk.vk, srs, asg.instances,
                                              proof)
        assert acc is not None and acc.check(srs)
        vctx = Context()
        vc = VerifierChip(RangeChip(lookup_bits=14))
        if msm == "host_msm":
            monkeypatch.setattr(vc.msm, "msm", _host_msm(vc))
        icells = [[vctx.load_witness(int(v)) for v in col]
                  for col in asg.instances]
        lhs, rhs = vc.verify_proof(vctx, pk.vk, srs, icells, proof)
        assert (lhs[0].value % P, lhs[1].value % P) == \
            (int(acc.lhs[0]), int(acc.lhs[1]))
        assert (rhs[0].value % P, rhs[1].value % P) == \
            (int(acc.rhs[0]), int(acc.rhs[1]))

    def test_invalid_proof_rejected_at_witness_time(self, inner):
        pk, srs, instances, proof = inner
        ctx = Context()
        rng = RangeChip(lookup_bits=14)
        vc = VerifierChip(rng)
        bad_cells = [[ctx.load_witness((int(v) + 1) % R)
                      for v in col] for col in instances]
        with pytest.raises(AssertionError):
            vc.verify_proof(ctx, pk.vk, srs, bad_cells, proof)

    def test_statement_layout(self, inner):
        pk, srs, instances, proof = inner
        args = AggregationArgs(inner_vk=pk.vk, srs=srs,
                               inner_instances=instances, proof=proof)
        stmt = AggregationCircuit.get_instances(args, None)
        assert len(stmt) == 12 + sum(len(c) for c in instances)
        acc = Accumulator.from_limbs(stmt[:12])
        assert acc.check(srs)
        assert stmt[12:] == [int(v) % R for col in instances for v in col]


@pytest.fixture(scope="module")
def inner2():
    """A second app circuit (different shape/vk) for multi-snark folds."""
    random.seed(8)
    ctx = Context()
    rng = RangeChip(lookup_bits=8)
    g = rng.gate
    a = ctx.load_witness(31)
    b = ctx.load_witness(64)
    c = g.add(ctx, g.mul(ctx, a, a), b)
    rng.range_check(ctx, c, 12)
    ctx.expose_public(c)
    cfg = ctx.auto_config(k=10, lookup_bits=8)
    asg = ctx.assignment(cfg)
    srs = SRS.unsafe_setup(10)
    pk = keygen(srs, cfg, asg.fixed, asg.selectors, asg.copies)
    proof = prove(pk, srs, asg, transcript=PoseidonTranscript())
    return pk, srs, asg.instances, proof


class TestMultiSnarkFold:
    @pytest.mark.parametrize("msm", [
        "host_msm", pytest.param("msm_chip", marks=pytest.mark.slow)])
    def test_fold_matches_native_accumulate(self, inner, inner2, msm,
                                            monkeypatch):
        """Two inner snarks (distinct vks) verified in-circuit; the
        transcript-bound RLC fold equals the native `accumulate` and the
        folded deferred pairing closes (reference: snark-verifier
        aggregating Vec<Snark> with N > 1).

        The fold is what is tested here, and it runs as it is. The two
        verifications before it repeat `test_accumulator_matches_native`'s
        in-circuit MSM over each proof (some 20 s of Python a proof, alone
        on a host): `host_msm` takes their sums from the host curve, as in
        `test_sha_region_inner_proof_aggregates`; `msm_chip`, in the slow
        tier, runs them whole."""

        pk1, srs, inst1, proof1 = inner
        pk2, _srs2, inst2, proof2 = inner2
        a1 = VerifierChip.native_accumulator(pk1.vk, srs, inst1, proof1)
        a2 = VerifierChip.native_accumulator(pk2.vk, srs, inst2, proof2)
        want = accumulate([a1, a2])
        assert want.check(srs)

        ctx = Context()
        vc = VerifierChip(RangeChip(lookup_bits=14))
        accs = []
        with monkeypatch.context() as mp:
            if msm == "host_msm":
                mp.setattr(vc.msm, "msm", _host_msm(vc))
            for pk, inst, proof in ((pk1, inst1, proof1),
                                    (pk2, inst2, proof2)):
                cells = [[ctx.load_witness(int(v)) for v in col]
                         for col in inst]
                accs.append(vc.verify_proof(ctx, pk.vk, srs, cells, proof))
        lhs, rhs = vc.fold_accumulators(ctx, accs)
        assert (lhs[0].value % P, lhs[1].value % P) == \
            (int(want.lhs[0]), int(want.lhs[1]))
        assert (rhs[0].value % P, rhs[1].value % P) == \
            (int(want.rhs[0]), int(want.rhs[1]))

    def test_multi_snark_statement_layout(self, inner, inner2):
        from spectre_tpu.models.aggregation import SnarkWitness

        pk1, srs, inst1, proof1 = inner
        pk2, _srs2, inst2, proof2 = inner2
        args = AggregationArgs(
            inner_vk=pk1.vk, srs=srs, inner_instances=inst1, proof=proof1,
            more_snarks=(SnarkWitness(pk2.vk, inst2, proof2),))
        stmt = AggregationCircuit.get_instances(args, None)
        n1 = sum(len(c) for c in inst1)
        n2 = sum(len(c) for c in inst2)
        assert len(stmt) == 12 + n1 + n2
        acc = Accumulator.from_limbs(stmt[:12])
        assert acc.check(srs)


@pytest.mark.skipif(not RUN_SLOW, reason="~6M-cell mock (set RUN_SLOW=1)")
class TestAggregationCircuitSlow:
    def test_mock_satisfied(self, inner):
        pk, srs, instances, proof = inner
        args = AggregationArgs(inner_vk=pk.vk, srs=srs,
                               inner_instances=instances, proof=proof)
        assert AggregationCircuit.mock(args, None, k=17)

    def test_outer_prove_verify(self, inner, tmp_path, monkeypatch):
        pk, srs, instances, proof = inner
        args = AggregationArgs(inner_vk=pk.vk, srs=srs,
                               inner_instances=instances, proof=proof)
        # BUILD_DIR is bound at import time; patch the module attribute so
        # pinning/pk artifacts land in tmp_path, not the repo build dir
        from spectre_tpu.models import app_circuit as ac
        monkeypatch.setattr(ac, "BUILD_DIR", str(tmp_path))
        srs17 = SRS.load_or_setup(17, str(tmp_path))
        opk = AggregationCircuit.create_pk(srs17, type("S", (), {
            "name": "test"}), 17, args, cache=False)
        oproof = AggregationCircuit.prove(opk, srs17, args, None)
        stmt = AggregationCircuit.get_instances(args, None)
        assert AggregationCircuit.verify(opk.vk, srs17, stmt, oproof)
        # wrong accumulator limb -> pairing fails
        bad = list(stmt)
        bad[0] = (bad[0] + 1) % R
        assert not AggregationCircuit.verify(opk.vk, srs17, bad, oproof)
