"""The Poseidon commitment to a sync committee (public input 0 of a
committee-update proof) in plain Python ints, from the compressed public
keys of the request alone. Imports nothing of the program.

The sponge is the one `lightclient-circuits/src/poseidon.rs` pins: width
12, rate 11, 8 full and 65 partial rounds, x^5, over the BN254 scalar
field; round constants and the MDS matrix by the Grain procedure of the
Poseidon paper's parameter script as halo2-base instantiates it (round
constants most-significant bit first with rejection; the MDS matrix's 2t
values least-significant bit first without, 1/(x_i + y_j)). What is hashed:
each key's x as two field elements (its low 312 bits, the rest), then the
keys' y-signs packed 253 to an element; a single 1 pads the last block.

Restates `spectre_tpu/ops/poseidon.py` (permute_native, PoseidonSponge) and
`gadgets/poseidon_commit.py`; `tests/test_reference.py` holds the two
against each other."""

from __future__ import annotations

import functools

from reference.bn254_g1 import R

WIDTH, RATE, FULL_ROUNDS, PARTIAL_ROUNDS = 12, 11, 8, 65
FIELD_BITS = 254
LOW_BITS = 3 * 104           # three limbs of 104 bits fold into the low element
SIGNS_PER_ELEMENT = 253


class _Grain:
    def __init__(self):
        self.bits = []
        for value, width in ((1, 2), (0, 4), (FIELD_BITS, 12), (WIDTH, 12),
                             (FULL_ROUNDS, 10), (PARTIAL_ROUNDS, 10),
                             ((1 << 30) - 1, 30)):
            self.bits += [(value >> (width - 1 - i)) & 1
                          for i in range(width)]
        for _ in range(160):
            self._step()

    def _step(self) -> int:
        b = self.bits
        new = b[62] ^ b[51] ^ b[38] ^ b[23] ^ b[13] ^ b[0]
        self.bits = b[1:] + [new]
        return new

    def bit(self) -> int:
        """The second of a pair of bits, where the first is 1."""
        while True:
            first, second = self._step(), self._step()
            if first:
                return second

    def element(self) -> int:
        while True:
            v = 0
            for _ in range(FIELD_BITS):
                v = (v << 1) | self.bit()
            if v < R:
                return v

    def element_unrejected(self) -> int:
        v = 0
        for i in range(FIELD_BITS):
            v |= self.bit() << i
        return v % R


@functools.cache
def constants() -> tuple:
    grain = _Grain()
    rounds = FULL_ROUNDS + PARTIAL_ROUNDS
    rc = [[grain.element() for _ in range(WIDTH)] for _ in range(rounds)]
    while True:
        vals = [grain.element_unrejected() for _ in range(2 * WIDTH)]
        if len(set(vals)) == 2 * WIDTH:
            break
    xs, ys = vals[:WIDTH], vals[WIDTH:]
    mds = [[pow(x + y, -1, R) for y in ys] for x in xs]
    return rc, mds


def permute(state: list) -> list:
    rc, mds = constants()
    half = FULL_ROUNDS // 2
    for r, consts in enumerate(rc):
        state = [(s + c) % R for s, c in zip(state, consts)]
        if r < half or r >= half + PARTIAL_ROUNDS:
            state = [pow(s, 5, R) for s in state]
        else:
            state[0] = pow(state[0], 5, R)
        state = [sum(m * s for m, s in zip(row, state)) % R for row in mds]
    return state


def sponge(values: list) -> int:
    state = [0] * WIDTH
    padded = [v % R for v in values] + [1]
    for off in range(0, len(padded), RATE):
        for i, v in enumerate(padded[off:off + RATE]):
            state[i + 1] = (state[i + 1] + v) % R
        state = permute(state)
    return state[1]


def committee_commitment(pubkeys: list) -> int:
    """From 48-byte compressed BLS12-381 G1 keys (flag bits: 0x80
    compressed, 0x20 the larger y)."""
    values, signs = [], []
    for pk in pubkeys:
        x = int.from_bytes(pk, "big") & ((1 << 381) - 1)
        values += [x & ((1 << LOW_BITS) - 1), x >> LOW_BITS]
        signs.append((pk[0] >> 5) & 1)
    for off in range(0, len(signs), SIGNS_PER_ELEMENT):
        values.append(sum(b << i for i, b in
                          enumerate(signs[off:off + SIGNS_PER_ELEMENT])))
    return sponge(values)
