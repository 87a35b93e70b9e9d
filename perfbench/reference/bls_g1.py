"""BLS12-381 G1 in plain Python ints: enough to make a sync committee's
public keys from secret scalars (pk = sk * G, 48-byte compressed). Imports
nothing of the program."""

from __future__ import annotations

from reference import weierstrass

P = 0x1a0111ea397fe69a4b1ba7b6434bacd764774b84f38512bf6730d2a0f6b0f6241eabfffeb153ffffb9feffffffffaaab
ORDER = 0x73eda753299d7d483339d80809a1d80553bda402fffe5bfeffffffff00000001
GX = 0x17f1d3a73197d7942695638c4fa9ac0fc3688c4f9774b905a14e3a3f171bac586c55e83ff97a1aeffb3af00adb22c6bb
GY = 0x08b3f481e3aaa0f1a09e30ed741d8ae4fcf5e095d5d00af600db18cb2c04b3edd03cc744a2888ae40caa232946c5e7e1
assert (GY * GY - GX * GX * GX - 4) % P == 0


def sk_to_pk(sk: int) -> bytes:
    sk %= ORDER
    assert sk, "zero secret key"
    x, y = weierstrass.mul((GX, GY), sk, P)
    flags = 0x80 | (0x20 if y > (P - 1) // 2 else 0)
    raw = bytearray(x.to_bytes(48, "big"))
    raw[0] |= flags
    return bytes(raw)
