"""BN254 G1 and Fr in plain Python ints, for the reference verifier. Points
are affine integer pairs, None the identity. Imports nothing of the
program."""

from __future__ import annotations

from reference import weierstrass

P = 21888242871839275222246405745257275088696311157297823662689037894645226208583
R = 21888242871839275222246405745257275088548364400416034343698204186575808495617
G = (1, 2)
FR_S = 28
FR_GENERATOR = 7
FR_ROOT_OF_UNITY = pow(FR_GENERATOR, (R - 1) >> FR_S, R)


def on_curve(pt) -> bool:
    return pt is None or (pt[1] * pt[1] - pt[0] * pt[0] * pt[0] - 3) % P == 0


def mul(pt, k: int):
    k %= R
    if pt is None or not k:
        return None
    return weierstrass.mul(pt, k, P)


def add(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return weierstrass.to_affine(
        weierstrass.add_affine((a[0], a[1], 1), b, P), P)


def neg(pt):
    return None if pt is None else (pt[0], (-pt[1]) % P)


def to_bytes(pt) -> bytes:
    if pt is None:
        return b"\x00" * 64
    return pt[0].to_bytes(32, "big") + pt[1].to_bytes(32, "big")


def from_bytes(b: bytes):
    if len(b) != 64:
        raise ValueError("g1 point must be 64 bytes")
    if b == b"\x00" * 64:
        return None
    pt = (int.from_bytes(b[:32], "big"), int.from_bytes(b[32:], "big"))
    if pt[0] >= P or pt[1] >= P or not on_curve(pt):
        raise ValueError("bad g1 point")
    return pt
