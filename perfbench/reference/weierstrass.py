"""Short Weierstrass curves y^2 = x^3 + b over a prime field, in plain
Python ints: Jacobian doubling and mixed addition, enough for scalar
multiplication of affine points. Shared by BN254 G1 (the proofs) and
BLS12-381 G1 (the committee's keys). Imports nothing of the program."""

from __future__ import annotations

IDENTITY = (0, 1, 0)


def double(pt, p: int):
    x, y, z = pt
    if not y or not z:
        return IDENTITY
    a = x * x % p
    b = y * y % p
    c = b * b % p
    d = 2 * ((x + b) * (x + b) - a - c) % p
    e = 3 * a % p
    x3 = (e * e - 2 * d) % p
    return (x3, (e * (d - x3) - 8 * c) % p, 2 * y * z % p)


def add_affine(pt, q, p: int):
    """Jacobian pt + affine q (q never the identity)."""
    x1, y1, z1 = pt
    if not z1:
        return (q[0], q[1], 1)
    z2 = z1 * z1 % p
    h = (q[0] * z2 - x1) % p
    r = (q[1] * z2 * z1 - y1) % p
    if not h:
        return double(pt, p) if not r else IDENTITY
    h2 = h * h % p
    h3 = h2 * h % p
    v = x1 * h2 % p
    x3 = (r * r - h3 - 2 * v) % p
    return (x3, (r * (v - x3) - y1 * h3) % p, z1 * h % p)


def to_affine(pt, p: int):
    if not pt[2]:
        return None
    zi = pow(pt[2], -1, p)
    return (pt[0] * zi * zi % p, pt[1] * zi * zi * zi % p)


def mul(q, k: int, p: int):
    """k * q for an affine q and k > 0; affine, or None for the identity."""
    acc = IDENTITY
    for bit in bin(k)[2:]:
        acc = double(acc, p)
        if bit == "1":
            acc = add_affine(acc, q, p)
    return to_affine(acc, p)
