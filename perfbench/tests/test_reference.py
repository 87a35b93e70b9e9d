"""The plain reference against a recorded proof, and its control.

`tiny_proof.json` is a committee-update proof of the 2-validator `tiny`
spec at k=13 (8 advice columns, 30 wide-SHA slots, nibble lookups: every
kind of identity the k=14 cell has), made by the program's CpuBackend, with
the verifying key's plain numbers and the public inputs.

The control breaks one guarantee the configuration states, each in turn; the
reference has to refuse every one."""

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import pytest  # noqa: E402

from harness import cells  # noqa: E402
from reference import bls_g1, bn254_g1 as g1, plonk, poseidon, ssz  # noqa: E402

SEED = "spectre-tpu-test-srs"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "tiny_proof.json")) as f:
        d = json.load(f)
    return (plonk.VerifyingKey(d["vk"]), bytes.fromhex(d["proof"]),
            [int(v, 16) for v in d["instances"]], d["vk"])


def test_recorded_proof_verifies(recorded):
    vk, proof, inst, _ = recorded
    assert plonk.verify(vk, plonk.unsafe_tau(SEED), [inst], proof) == ""


def _flip(proof: bytes, pos: int) -> bytes:
    bad = bytearray(proof)
    bad[pos] ^= 1
    return bytes(bad)


@pytest.mark.parametrize("name", [
    "commitment_altered", "evaluation_altered", "opening_altered",
    "other_header", "other_committee", "other_setup", "truncated",
    "trailing_byte"])
def test_control_is_refused(recorded, name):
    vk, proof, inst, _ = recorded
    tau = plonk.unsafe_tau(SEED)
    n_commit = plonk.commitment_plan(vk.shape)[3]
    if name == "commitment_altered":      # a point of the advice commitments
        proof = _flip(proof, 64 * 3 + 40)
    elif name == "evaluation_altered":    # a scalar of the eval section
        proof = _flip(proof, 64 * n_commit + 32 * 5 + 31)
    elif name == "opening_altered":       # W2, the last point
        w2 = g1.from_bytes(proof[-64:])
        proof = proof[:-64] + g1.to_bytes(g1.add(w2, g1.G))
    elif name == "other_header":          # the proof of another request
        inst = [inst[0], inst[1] ^ 1, inst[2]]
    elif name == "other_committee":
        inst = [(inst[0] + 1) % g1.R, inst[1], inst[2]]
    elif name == "other_setup":           # proved against another tau
        tau += 1
    elif name == "truncated":
        proof = proof[:-32]
    elif name == "trailing_byte":
        proof = proof + b"\x00"
    assert plonk.verify(vk, tau, [inst], proof) != ""


def test_a_changed_circuit_changes_the_digest(recorded):
    vk, _, _, numbers = recorded
    weaker = json.loads(json.dumps(numbers))
    weaker["selector_commits"][0] = None      # a gate switched off
    assert plonk.VerifyingKey(weaker).digest() != vk.digest()
    fewer = json.loads(json.dumps(numbers))
    fewer["shape"]["num_sha_slots"] -= 1
    assert plonk.VerifyingKey(fewer).digest() != vk.digest()


def test_header_root_by_hand():
    # eight leaves: slot, proposer, three roots, three zero chunks
    h = {"slot": 5, "proposer_index": 7, "parent_root": "0x" + "11" * 32,
         "state_root": "0x" + "33" * 32, "body_root": "0x" + "22" * 32}
    leaves = [ssz.uint64_chunk(5), ssz.uint64_chunk(7), b"\x11" * 32,
              b"\x33" * 32, b"\x22" * 32] + [ssz.ZERO32] * 3
    l1 = [ssz.sha(leaves[i] + leaves[i + 1]) for i in (0, 2, 4, 6)]
    l2 = [ssz.sha(l1[0] + l1[1]), ssz.sha(l1[2] + l1[3])]
    assert ssz.header_root(h) == ssz.sha(l2[0] + l2[1])


def test_committee_commitment_golden():
    """32 keys, sk = 1000 + 7 i. The number was read once from this code and
    from the program's `committee_poseidon_from_uncompressed`, which agreed
    (tests/test_requests.py holds the two against each other on every run)."""
    keys = [bls_g1.sk_to_pk(1000 + 7 * i) for i in range(32)]
    assert poseidon.committee_commitment(keys) == \
        0x10d9520241f4ab26443fe6553b4874ad22a71873173bf5450bab4edbdf46a8e7
    assert poseidon.committee_commitment(keys[::-1]) != \
        poseidon.committee_commitment(keys)


def test_the_judge_recomputes_the_committee_commitment(recorded):
    """Public input 0 is worked out from the request's keys, not taken from
    the service: a result that states another committee's commitment is
    refused before the verifier is asked."""
    vk, proof, inst, numbers = recorded
    sizes = {"sync_committee_size": 2, "sync_committee_pubkeys_depth": 6,
             "sync_committee_pubkeys_root_index": 110}
    config = {"spec_sizes": sizes, "vk_digest": vk.digest().hex(),
              "srs": {"seed": SEED}}
    req = cells.load_plugin("requests", "committee_update").make(config, 3, 0)
    ref = cells.load_plugin("reference", "committee_update") \
        .Reference(config, numbers)
    assert ref.vk_ok
    keys = [bytes.fromhex(k[2:]) for k in req["params"][
        "light_client_update"]["next_sync_committee"]["pubkeys"]]
    mine = poseidon.committee_commitment(keys)
    roots = [req["expected_instances"][1], req["expected_instances"][2]]
    result = {"proof": "0x" + proof.hex(),
              "instances": [hex(v) for v in [inst[0]] + roots],
              "committee_poseidon": hex(inst[0])}
    assert ref.check(req, result) == "public input 0 is not the request's"
    # with the right commitment it gets as far as the verifier, which
    # refuses the recorded proof for these inputs
    result = dict(result, instances=[hex(v) for v in [mine] + roots],
                  committee_poseidon=hex(mine))
    why = ref.check(req, result)
    assert why and "public input" not in why
    # and a result for another header is refused by the header's root
    other = dict(req, expected_instances={1: roots[0] ^ 1, 2: roots[1]})
    assert ref.check(other, result) == "public input 1 is not the request's"
