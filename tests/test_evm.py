"""EVM verifier generation + execution-oracle tests.

Reference parity: the reference golden-tests its generated Yul via revm
(`evm_verify`); offline we execute the generated Solidity subset through
evm/simulator.py against real Keccak-transcript proofs."""


import pytest

from spectre_tpu.evm import encode_calldata, gen_evm_verifier
from spectre_tpu.evm.simulator import run_verifier
from spectre_tpu.plonk.constraint_system import Assignment, CircuitConfig
from spectre_tpu.plonk.keygen import keygen
from spectre_tpu.plonk.prover import prove
from spectre_tpu.plonk.transcript import KeccakTranscript, keccak256
from spectre_tpu.plonk.verifier import verify

from _shapes import TINY_K as K  # noqa: E402  (the shared tiny SRS's k)


@pytest.fixture(scope="module")
def setup(tiny):
    srs, pk, out = tiny.srs, tiny.pk, tiny.out
    proof = prove(pk, srs, tiny.asg, transcript=KeccakTranscript())
    assert verify(pk.vk, srs, [[out]], proof, transcript_cls=KeccakTranscript)
    src = gen_evm_verifier(pk.vk, srs, num_instances=1)
    return srs, pk, out, proof, src


class TestCodegen:
    def test_deterministic_and_wellformed(self, setup):
        srs, pk, out, proof, src = setup
        assert src == gen_evm_verifier(pk.vk, srs, num_instances=1)
        assert src.count("{") == src.count("}")
        assert "0x" + pk.vk.digest().hex() in src          # vk binding
        assert f"require(proof.length == {len(proof)}" in src
        assert "pragma solidity" in src and "function verify" in src

    def test_generated_verifier_accepts_real_proof(self, setup):
        srs, pk, out, proof, src = setup
        assert run_verifier(src, [out], proof)

    def test_generated_verifier_rejects_forgeries(self, setup):
        srs, pk, out, proof, src = setup
        # tampered commitment section
        bad = bytearray(proof)
        bad[100] ^= 1
        assert not run_verifier(src, [out], bytes(bad))
        # tampered eval section
        bad2 = bytearray(proof)
        bad2[-100] ^= 1
        assert not run_verifier(src, [out], bytes(bad2))
        # wrong public input
        assert not run_verifier(src, [out + 1], proof)
        # wrong length
        assert not run_verifier(src, [out], proof + b"\x00" * 32)

    def test_multi_column_circuit(self, setup):
        # wider shape: 2 advice columns (multi perm chunks path)
        srs = setup[0]
        cfg = CircuitConfig(k=K, num_advice=2, num_lookup_advice=1,
                            num_fixed=1, lookup_bits=4)
        n = cfg.n
        advice = [[0] * n, [0] * n]
        selectors = [[0] * n, [0] * n]
        advice[0][0:4] = [2, 3, 4, 14]
        selectors[0][0] = 1
        advice[1][0:4] = [14, 14, 1, 28]
        selectors[1][0] = 1
        lookup = [[0] * n]
        lookup[0][0] = 14
        fixed = [[0] * n]
        copies = [
            ((cfg.col_gate_advice(0), 3), (cfg.col_gate_advice(1), 0)),
            ((cfg.col_gate_advice(1), 0), (cfg.col_gate_advice(1), 1)),
            ((cfg.col_gate_advice(0), 3), (cfg.col_lookup_advice(0), 0)),
            ((cfg.col_instance(0), 0), (cfg.col_gate_advice(1), 3)),
        ]
        pk = keygen(srs, cfg, fixed, selectors, copies)
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[28]], copies)
        proof = prove(pk, srs, asg, transcript=KeccakTranscript())
        src = gen_evm_verifier(pk.vk, srs, num_instances=1)
        assert run_verifier(src, [28], proof)
        assert not run_verifier(src, [29], proof)


class TestAccumulatorPairing:
    """num_acc_limbs=12: the generated contract must ALSO perform the
    deferred KZG pairing over the first 12 instances — an outer-valid proof
    wrapping a pairing-INVALID accumulator must be rejected (review finding:
    without this, compressed proofs over forged inner proofs verified)."""

    @staticmethod
    def _acc_proof(srs, s: int, valid: bool):
        from spectre_tpu.builder import Context
        from spectre_tpu.fields import bn254
        from spectre_tpu.models.aggregation import Accumulator

        from spectre_tpu.native import host

        g1 = bn254.g1_curve
        lhs = g1.mul(bn254.G1_GEN, s)          # [s] G1
        if valid:
            tau_g = host.limbs_to_ints(srs.g1_powers[1:2].reshape(2, 4))
            rhs = g1.mul((bn254.Fq(tau_g[0]), bn254.Fq(tau_g[1])), s)
        else:
            rhs = g1.mul(bn254.G1_GEN, s + 1)  # wrong: pairing fails
        acc = Accumulator(lhs=lhs, rhs=rhs)
        if valid:
            assert acc.check(srs)
        else:
            assert not acc.check(srs)

        ctx = Context()
        for v in acc.limbs():
            ctx.expose_public(ctx.load_witness(v))
        cfg = ctx.auto_config(k=K, lookup_bits=4)
        advice, lookup, fixed, selectors, copies, instances, _bp = \
            ctx.layout(cfg)
        pk = keygen(srs, cfg, fixed, selectors, copies)
        asg = Assignment(cfg, advice, lookup, fixed, selectors, instances,
                         copies)
        proof = prove(pk, srs, asg, transcript=KeccakTranscript())
        assert verify(pk.vk, srs, instances, proof,
                      transcript_cls=KeccakTranscript)
        src = gen_evm_verifier(pk.vk, srs, num_instances=12,
                               num_acc_limbs=12)
        return src, instances[0], proof

    def test_valid_accumulator_accepted(self, setup):
        srs = setup[0]
        src, inst, proof = self._acc_proof(srs, 12345, valid=True)
        assert run_verifier(src, inst, proof)

    def test_invalid_accumulator_rejected_despite_valid_outer(self, setup):
        srs = setup[0]
        src, inst, proof = self._acc_proof(srs, 12345, valid=False)
        # the outer PLONK proof itself is valid — only the deferred
        # accumulator pairing must reject it
        assert not run_verifier(src, inst, proof)


class TestCalldata:
    def test_layout_golden(self, setup):
        _, _, out, proof, _ = setup
        cd = encode_calldata([out], proof)
        assert cd[:4] == keccak256(b"verify(uint256[],bytes)")[:4]
        # head: two offsets
        assert int.from_bytes(cd[4:36], "big") == 64
        inst_off = 64
        assert int.from_bytes(cd[4 + 32:4 + 64], "big") == \
            inst_off + 32 + 32 * 1
        # instances array
        assert int.from_bytes(cd[4 + 64:4 + 96], "big") == 1
        assert int.from_bytes(cd[4 + 96:4 + 128], "big") == out
        # proof bytes
        assert int.from_bytes(cd[4 + 128:4 + 160], "big") == len(proof)
        assert cd[4 + 160:4 + 160 + len(proof)] == proof
        assert len(cd) % 32 == 4


class TestGasAndSizeEstimation:
    """Static gas/deployed-size model (evm/gas.py; reference prints these
    from revm, `prover/src/cli.rs:249-277`)."""

    def test_counts_and_gas_on_generated_verifier(self, setup):
        from spectre_tpu.evm import estimate_deployed_size, estimate_gas
        _, pk, out, proof, src = setup
        cd = encode_calldata([out], proof)
        g = estimate_gas(src, calldata=cd)
        c = g["counts"]
        # the verifier must contain the structural minimum: a pairing, the
        # SHPLONK W/W' ecMuls, transcript keccaks, and the identity's mulmods
        assert c["pairing"] >= 1
        assert c["ecmul"] >= 2
        assert c["keccak"] >= 3
        assert c["mulmod"] > 10
        assert g["gas_precompiles"] >= 45000 + 34000 * 2
        assert g["gas_total"] > g["gas_execution"] > 0
        assert g["gas_intrinsic"] >= 21000
        sz = estimate_deployed_size(src)
        assert sz["deployed_bytes_estimate"] > 2200
        assert sz["deployed_size_risk"] in ("ok", "tight", "exceeds-eip170")

    def test_flagship_scale_verifier_size_assessment(self):
        """The archived flagship aggregation verifier (107KB source) gets a
        concrete EIP-170 assessment instead of an unknown."""
        import glob
        import os
        from spectre_tpu.evm import estimate_deployed_size
        cands = sorted(glob.glob(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "build", "**", "aggregation_sync_step_*_verifier.sol"),
            recursive=True))
        if not cands:
            import pytest
            pytest.skip("no flagship verifier source in build/")
        with open(cands[-1]) as f:
            src = f.read()
        sz = estimate_deployed_size(src)
        # record-keeping assertion: the estimate must be decided, whatever
        # the verdict — the flagship record embeds it
        assert sz["deployed_size_risk"] in ("ok", "tight", "exceeds-eip170")
        assert sz["deployed_bytes_estimate"] > 0
