"""Committee-update requests from a seed: a valid `light_client_update` in
the JSON shape the prover's RPC takes, and the public inputs the proof for
it must carry. Made with the benchmark's own plain code (reference/): the
program sees only the request.

Every request has the same sizes whatever the seed: `sync_committee_size`
keys, a branch of `sync_committee_depth + 1` nodes at the pubkeys depth (so
no aggregate-pubkey extension applies). What differs between requests is the
committee (so no two dedup onto one job) and the header's slot."""

from __future__ import annotations

import hashlib

from reference import bls_g1, ssz

METHOD = "genEvmProof_CommitteeUpdateCompressed"
SUBMIT_METHOD = "submitProof_CommitteeUpdateCompressed"


def _hex(b: bytes) -> str:
    return "0x" + b.hex()


def make(config: dict, seed: int, index) -> dict:
    """Request number `index` of the run with `seed` (`index` may be a word,
    as for the warm-up's committee, which the window never uses)."""
    spec = config["spec_sizes"]
    n = spec["sync_committee_size"]
    tag = f"perfbench/{seed}/{index}/".encode()
    pubkeys = [bls_g1.sk_to_pk(int.from_bytes(
        hashlib.sha256(tag + str(i).encode()).digest(), "big"))
        for i in range(n)]
    depth = spec["sync_committee_pubkeys_depth"]
    gindex = spec["sync_committee_pubkeys_root_index"]
    branch = [hashlib.sha256(tag + b"branch" + bytes([d])).digest()
              for d in range(depth)]
    state_root = ssz.root_from_branch(ssz.pubkeys_root(pubkeys), branch,
                                      gindex)
    slot = int.from_bytes(hashlib.sha256(tag + b"slot").digest()[:4], "big")
    header = {"slot": slot, "proposer_index": 7,
              "parent_root": _hex(b"\x11" * 32),
              "state_root": _hex(state_root),
              "body_root": _hex(b"\x22" * 32)}
    pk_hex = [_hex(pk) for pk in pubkeys]
    update = {"finalized_header": header,
              "next_sync_committee": {"pubkeys": pk_hex,
                                      "aggregate_pubkey": pk_hex[0]},
              "next_sync_committee_branch": [_hex(b) for b in branch]}
    root = ssz.header_root(header)
    return {"params": {"light_client_update": update},
            # instances = [committee poseidon, root low 128, root high 128]:
            # the last two are known from the request alone; the proof
            # binds the first to the same header through the circuit
            "expected_instances": {1: int.from_bytes(root[16:], "big"),
                                   2: int.from_bytes(root[:16], "big")}}
