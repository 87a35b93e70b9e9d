"""Plain SSZ pieces (hashlib only): what a committee-update request's
public inputs are made of. Imports nothing of the program."""

from __future__ import annotations

import hashlib

ZERO32 = b"\x00" * 32


def sha(b: bytes) -> bytes:
    return hashlib.sha256(b).digest()


def merkleize(chunks: list, limit: int | None = None) -> bytes:
    """Root of 32-byte chunks padded with zero chunks to `limit` (or the next
    power of two) leaves."""
    n = limit if limit is not None else max(1, len(chunks))
    width = 1
    while width < n:
        width *= 2
    layer = list(chunks) + [ZERO32] * (width - len(chunks))
    while len(layer) > 1:
        layer = [sha(layer[i] + layer[i + 1])
                 for i in range(0, len(layer), 2)]
    return layer[0]


def uint64_chunk(v: int) -> bytes:
    return int(v).to_bytes(8, "little") + b"\x00" * 24


def header_root(h: dict) -> bytes:
    """hash_tree_root(BeaconBlockHeader): five fields, eight leaves."""
    return merkleize([uint64_chunk(h["slot"]),
                      uint64_chunk(h["proposer_index"]),
                      bytes.fromhex(h["parent_root"][2:]),
                      bytes.fromhex(h["state_root"][2:]),
                      bytes.fromhex(h["body_root"][2:])], limit=8)


def bytes48_root(b: bytes) -> bytes:
    return sha(b[:32] + b[32:] + b"\x00" * 16)


def pubkeys_root(pubkeys: list) -> bytes:
    """hash_tree_root(Vector[BLSPubkey, N])."""
    return merkleize([bytes48_root(pk) for pk in pubkeys])


def root_from_branch(leaf: bytes, branch: list, gindex: int) -> bytes:
    node = leaf
    for sib in branch:
        node = sha(sib + node) if gindex & 1 else sha(node + sib)
        gindex >>= 1
    return node
