"""The plain reference for a served proof: a PLONK/SHPLONK verifier in
Python ints, for the circuit family this prover serves (vertical gate,
chunked permutation, range/nibble lookups, the wide SHA-256 region, Blake2b
transcript). It imports nothing of the program. It follows the protocol as
`plonk/verifier.py`, `expressions.py`, `keygen.py` (plans), `kzg.py`
(SHPLONK) and `transcript.py` state it; the originals are listed under
Open questions in PERF.md.

Where the program's verifier ends in a pairing with the SRS's G2 points,
this one knows the set-up's secret: the configuration states an UNSAFE
set-up from a public seed (as the upstream's `unsafe-setup`), so
e(A, [tau]_2) == e(B, [1]_2) is checked as tau*A == B in G1. Nothing of the
program's SRS is taken; a service that proved against another tau fails."""

from __future__ import annotations

import hashlib

from reference import bn254_g1 as g1

R = g1.R
ROT_LAST = "last"
PERM_CHUNK = 2
NUM_H_CHUNKS = 3
ZK_ROWS = 5
SHA_BIT_COLS, SHA_WORD_COLS = 104, 10
SHA_SEED_ROW, SHA_OUT_ROW = 3, 68
SHA_NUM_SELECTORS = 7
SHA_W, SHA_A, SHA_E, SHA_CARRY = 0, 32, 64, 96
SHA_ACT_WORD = 9
DELTA = pow(g1.FR_GENERATOR, 1 << g1.FR_S, R)


def unsafe_tau(seed: str) -> int:
    return int.from_bytes(hashlib.sha256(seed.encode()).digest() * 2,
                          "big") % R


class Shape:
    """The circuit's shape, from the verifying key's plain numbers."""

    def __init__(self, d: dict):
        self.k = d["k"]
        self.num_advice = d["num_advice"]
        self.num_lookup_advice = d["num_lookup_advice"]
        self.num_fixed = d["num_fixed"]
        self.lookup_bits = d["lookup_bits"]
        self.num_instance = d["num_instance"]
        self.lookup_tables = tuple(d["lookup_tables"])
        self.num_sha_slots = d["num_sha_slots"]
        self.n = 1 << self.k
        zk = ZK_ROWS + 2 if self.num_sha_slots else ZK_ROWS
        self.usable_rows = self.n - zk - 1
        self.last_row = self.usable_rows
        self.num_sha_word = SHA_WORD_COLS if self.num_sha_slots else 0
        self.num_sha_bit = SHA_BIT_COLS if self.num_sha_slots else 0
        self.num_perm_columns = (self.num_advice + self.num_lookup_advice
                                 + self.num_fixed + self.num_sha_word
                                 + self.num_instance)
        self.num_perm_chunks = -(-self.num_perm_columns // PERM_CHUNK)
        self.omega = pow(g1.FR_ROOT_OF_UNITY, 1 << (g1.FR_S - self.k), R)
        self.omega_inv = pow(self.omega, -1, R)

    def rotation_point(self, x: int, rot) -> int:
        if rot == ROT_LAST:
            return pow(self.omega, self.last_row, R) * x % R
        if rot < 0:
            return pow(self.omega_inv, -rot, R) * x % R
        return pow(self.omega, rot, R) * x % R

    def lagrange(self, x: int, rows) -> dict:
        zx = (pow(x, self.n, R) - 1) % R
        ninv = pow(self.n, -1, R)
        out = {}
        for i in rows:
            wi = pow(self.omega, i, R)
            if (x - wi) % R == 0:
                out[i] = 1
            elif zx == 0:
                out[i] = 0
            else:
                out[i] = wi * zx % R * pow((x - wi) % R, -1, R) % R * ninv % R
        return out


class VerifyingKey:
    def __init__(self, d: dict):
        self.shape = Shape(d["shape"])
        self.raw = d
        self.selector = d["selector_commits"]
        self.fixed = d["fixed_commits"]
        self.sigma = d["sigma_commits"]
        self.table = d["table_commits"]
        self.sha_selector = d["sha_selector_commits"]
        self.sha_k = d["sha_k_commit"]
        for pt in (self.selector + self.fixed + self.sigma + self.table
                   + self.sha_selector + [self.sha_k]):
            if pt is not None and not g1.on_curve(tuple(pt)):
                raise ValueError("verifying key holds a point off the curve")

    def digest(self) -> bytes:
        s = self.shape
        h = hashlib.blake2b(digest_size=32)
        h.update(repr((s.k, s.num_advice, s.num_lookup_advice, s.num_fixed,
                       s.lookup_bits, s.num_instance,
                       s.num_sha_slots)).encode())
        h.update(repr(s.lookup_tables).encode())
        for pt in (self.selector + self.fixed + self.sigma + self.table
                   + self.sha_selector
                   + ([self.sha_k] if s.num_sha_slots else [])):
            h.update(g1.to_bytes(None if pt is None else tuple(pt)))
        return h.digest()

    def fixed_commitments(self) -> dict:
        out = {}
        for tag, lst in (("tab", self.table), ("q", self.selector),
                         ("fix", self.fixed), ("sig", self.sigma),
                         ("shq", self.sha_selector)):
            for j, c in enumerate(lst):
                out[(tag, j)] = None if c is None else tuple(c)
        if self.shape.num_sha_slots:
            out[("shk", 0)] = None if self.sha_k is None else tuple(self.sha_k)
        return out


def commitment_plan(s: Shape):
    keys = [("adv", j) for j in range(s.num_advice)]
    keys += [("ladv", j) for j in range(s.num_lookup_advice)]
    keys += [("shb", j) for j in range(s.num_sha_bit)]
    keys += [("shw", j) for j in range(s.num_sha_word)]
    for j in range(s.num_lookup_advice):
        keys += [("pA", j), ("pT", j)]
    pre_bg = len(keys)
    keys += [("pz", c) for c in range(s.num_perm_chunks)]
    keys += [("lz", j) for j in range(s.num_lookup_advice)]
    pre_y = len(keys)
    keys += [("h", i) for i in range(NUM_H_CHUNKS)]
    return keys, pre_bg, pre_y, len(keys)


def query_plan(s: Shape):
    plan = []
    for j in range(s.num_advice):
        plan += [(("adv", j), rot) for rot in (0, 1, 2, 3)]
    for j in range(s.num_lookup_advice):
        plan += [(("ladv", j), 0), (("pA", j), 0), (("pA", j), -1),
                 (("pT", j), 0), (("lz", j), 0), (("lz", j), 1)]
    for c in range(s.num_perm_chunks):
        plan += [(("pz", c), 0), (("pz", c), 1)]
        if c + 1 < s.num_perm_chunks:
            plan.append((("pz", c), ROT_LAST))
    plan += [(("q", j), 0) for j in range(s.num_advice)]
    plan += [(("fix", j), 0) for j in range(s.num_fixed)]
    plan += [(("sig", j), 0) for j in range(s.num_perm_columns)]
    plan += [(("tab", j), 0) for j in range(s.num_lookup_advice)]
    if s.num_sha_slots:
        for i in range(32):
            plan += [(("shb", SHA_W + i), r) for r in (0, -2, -7, -15, -16)]
        for i in range(32):
            plan += [(("shb", SHA_A + i), r) for r in (0, -1, -2, -3, -4)]
        for i in range(32):
            plan += [(("shb", SHA_E + i), r) for r in (0, -1, -2, -3, -4)]
        plan += [(("shb", SHA_CARRY + i), 0) for i in range(8)]
        back = SHA_SEED_ROW - SHA_OUT_ROW
        for j in range(8):
            plan += [(("shw", j), 0), (("shw", j), back)]
        plan += [(("shw", 8), 0), (("shw", SHA_ACT_WORD), 0),
                 (("shw", SHA_ACT_WORD), -1)]
        plan += [(("shq", i), 0) for i in range(SHA_NUM_SELECTORS)]
        plan.append((("shk", 0), 0))
    plan += [(("h", i), 0) for i in range(NUM_H_CHUNKS)]
    return plan


class Transcript:
    """The Blake2b transcript, reading a proof."""

    def __init__(self, proof: bytes):
        self.h = hashlib.blake2b(b"spectre-tpu-transcript-v1", digest_size=64)
        self.buf, self.pos, self.counter = proof, 0, 0

    def absorb(self, b: bytes):
        self.h.update(b)

    def _take(self, n: int) -> bytes:
        if self.pos + n > len(self.buf):
            raise ValueError("proof too short")
        out = self.buf[self.pos:self.pos + n]
        self.pos += n
        return out

    def common_scalar(self, v: int):
        self.absorb(b"S" + (v % R).to_bytes(32, "big"))

    def read_point(self):
        raw = self._take(64)
        pt = g1.from_bytes(raw)
        self.absorb(b"P" + raw)
        return pt

    def read_scalar(self) -> int:
        v = int.from_bytes(self._take(32), "big")
        if v >= R:
            raise ValueError("non-canonical scalar in proof")
        self.common_scalar(v)
        return v

    def challenge(self) -> int:
        self.counter += 1
        self.absorb(b"C" + self.counter.to_bytes(4, "big"))
        return int.from_bytes(self.h.copy().digest(), "big") % R


def identities(s: Shape, ev, l0, llast, lblind, x, beta, gamma):
    """The ordered constraint values at x (gate, permutation, lookups, SHA
    region). `ev(key, rot)` is the proof's evaluation."""
    for j in range(s.num_advice):
        yield ev(("q", j), 0) * (ev(("adv", j), 0) + ev(("adv", j), 1)
                                 * ev(("adv", j), 2) - ev(("adv", j), 3)) % R
    cols = ([("adv", j) for j in range(s.num_advice)]
            + [("ladv", j) for j in range(s.num_lookup_advice)]
            + [("fix", j) for j in range(s.num_fixed)]
            + [("shw", j) for j in range(s.num_sha_word)]
            + [("inst", j) for j in range(s.num_instance)])
    nch = s.num_perm_chunks
    act = (1 - llast - lblind) % R
    yield l0 * (ev(("pz", 0), 0) - 1) % R
    for ch in range(1, nch):
        yield l0 * (ev(("pz", ch), 0) - ev(("pz", ch - 1), ROT_LAST)) % R
    for ch in range(nch):
        left, right = ev(("pz", ch), 1), ev(("pz", ch), 0)
        for gidx in range(ch * PERM_CHUNK,
                          min((ch + 1) * PERM_CHUNK, len(cols))):
            v = ev(cols[gidx], 0)
            left = left * (v + beta * ev(("sig", gidx), 0) + gamma) % R
            right = right * (v + beta * pow(DELTA, gidx, R) % R * x
                             + gamma) % R
        yield act * (left - right) % R
    zl = ev(("pz", nch - 1), 0)
    yield llast * (zl * zl - zl) % R
    for j in range(s.num_lookup_advice):
        a, pa, pa_prev = ev(("ladv", j), 0), ev(("pA", j), 0), ev(("pA", j), -1)
        pt, tab = ev(("pT", j), 0), ev(("tab", j), 0)
        lz, lz1 = ev(("lz", j), 0), ev(("lz", j), 1)
        yield l0 * (lz - 1) % R
        yield act * (lz1 * (pa + beta) * (pt + gamma)
                     - lz * (a + beta) * (tab + gamma)) % R
        yield llast * (lz * lz - lz) % R
        yield l0 * (pa - pt) % R
        yield act * (pa - pt) * (pa - pa_prev) % R
    if s.num_sha_slots:
        yield from sha_identities(ev)


def sha_identities(ev):
    def w(i, rot=0):
        return ev(("shb", SHA_W + i), rot)

    def a(i, rot=0):
        return ev(("shb", SHA_A + i), rot)

    def e(i, rot=0):
        return ev(("shb", SHA_E + i), rot)

    def carry(i):
        return ev(("shb", SHA_CARRY + i), 0)

    def q(i):
        return ev(("shq", i), 0)

    def xor2(p, r):
        return p + r - 2 * p * r

    def xor3(p, r, t):
        return p + r + t - 2 * (p * r + r * t + t * p) + 4 * p * r * t

    def word(bit, rot=0):
        return sum(bit(i, rot) << i for i in range(32))

    for j in range(SHA_BIT_COLS):
        b = ev(("shb", j), 0)
        yield q(0) * (b * b - b) % R
    act = ev(("shw", SHA_ACT_WORD), 0)
    yield q(0) * (act * act - act) % R
    yield q(6) * (act - ev(("shw", SHA_ACT_WORD), -1)) % R
    for j in range(4):
        yield q(1) * (word(a, -j) - ev(("shw", j), 0)) % R
        yield q(1) * (word(e, -j) - ev(("shw", 4 + j), 0)) % R
    yield q(4) * (word(w) - ev(("shw", 8), 0)) % R
    sig1 = word(lambda i, _r: xor3(e((i + 6) % 32, -1), e((i + 11) % 32, -1),
                                   e((i + 25) % 32, -1)))
    ch = word(lambda i, _r: e(i, -3) + e(i, -1) * (e(i, -2) - e(i, -3)))
    ce = sum(carry(i) << (32 + i) for i in range(3))
    yield q(2) * (word(e) + ce - (word(a, -4) + word(e, -4) + sig1 + ch
                                  + ev(("shk", 0), 0) * act + word(w))) % R
    sig0 = word(lambda i, _r: xor3(a((i + 2) % 32, -1), a((i + 13) % 32, -1),
                                   a((i + 22) % 32, -1)))

    def maj(i, _r):
        b1, b2, b3 = a(i, -1), a(i, -2), a(i, -3)
        return b1 * b2 + b1 * b3 + b2 * b3 - 2 * b1 * b2 * b3

    ca = sum(carry(3 + i) << (32 + i) for i in range(3))
    yield q(2) * (word(a) + ca + word(a, -4)
                  - (word(e) + ce + sig0 + word(maj))) % R

    def s0(i, _r):
        p, r = w((i + 7) % 32, -15), w((i + 18) % 32, -15)
        return xor3(p, r, w(i + 3, -15)) if i <= 28 else xor2(p, r)

    def s1(i, _r):
        p, r = w((i + 17) % 32, -2), w((i + 19) % 32, -2)
        return xor3(p, r, w(i + 10, -2)) if i <= 21 else xor2(p, r)

    cs = sum(carry(6 + i) << (32 + i) for i in range(2))
    yield q(3) * (word(w) + cs - (word(w, -16) + word(s0) + word(w, -7)
                                  + word(s1))) % R
    back = SHA_SEED_ROW - SHA_OUT_ROW
    for j in range(8):
        fin = word(a if j < 4 else e, -(1 + (j % 4)))
        yield q(5) * (ev(("shw", j), 0) + (carry(j) << 32)
                      - (ev(("shw", j), back) + fin)) % R


def _interp_eval(points, evals, u: int) -> int:
    """The interpolant through (points, evals), at u."""
    total = 0
    for j, xj in enumerate(points):
        num = den = 1
        for m, xm in enumerate(points):
            if m != j:
                num = num * (u - xm) % R
                den = den * (xj - xm) % R
        total = (total + evals[j] * num % R * pow(den, -1, R)) % R
    return total


def _z(points, u: int) -> int:
    out = 1
    for p in points:
        out = out * (u - p) % R
    return out


def verify(vk: VerifyingKey, tau: int, instances: list, proof: bytes) -> str:
    """'' when the proof verifies for `instances` (one list per instance
    column), else what failed."""
    try:
        return _verify(vk, tau, instances, proof)
    except ValueError as exc:
        return f"malformed proof: {exc}"


def _verify(vk, tau, instances, proof) -> str:
    s = vk.shape
    tr = Transcript(proof)
    tr.absorb(vk.digest())
    for col in instances:
        if len(col) > s.usable_rows:
            return "too many public inputs"
        for v in col:
            tr.common_scalar(int(v))
    keys, pre_bg, pre_y, pre_x = commitment_plan(s)
    commits = {}
    for key in keys[:pre_bg]:
        commits[key] = tr.read_point()
    beta, gamma = tr.challenge(), tr.challenge()
    for key in keys[pre_bg:pre_y]:
        commits[key] = tr.read_point()
    y = tr.challenge()
    for key in keys[pre_y:pre_x]:
        commits[key] = tr.read_point()
    x = tr.challenge()
    plan = query_plan(s)
    evals = {}
    for key, rot in plan:
        evals[(key, rot)] = tr.read_scalar()
    for j in range(s.num_instance):
        lag = s.lagrange(x, range(len(instances[j])))
        evals[(("inst", j), 0)] = sum(
            int(v) * lag[i] for i, v in enumerate(instances[j])) % R
    special = s.lagrange(x, [0, s.last_row]
                         + list(range(s.usable_rows + 1, s.n)))
    l0, llast = special[0], special[s.last_row]
    lblind = sum(special[i] for i in range(s.usable_rows + 1, s.n)) % R
    acc = 0
    for value in identities(s, lambda key, rot: evals[(key, rot)], l0, llast,
                            lblind, x, beta, gamma):
        acc = (acc * y + value) % R
    xn = pow(x, s.n, R)
    h_at_x = (evals[(("h", 0), 0)] + xn * evals[(("h", 1), 0)]
              + xn * xn % R * evals[(("h", 2), 0)]) % R
    if acc != h_at_x * (xn - 1) % R:
        return "the constraint identity does not hold at x"

    # SHPLONK: one entry per committed polynomial, opened at its points
    fixed = vk.fixed_commitments()
    by_key: dict = {}
    for key, rot in plan:
        by_key.setdefault(key, []).append(rot)
    entries = []
    for key, rots in by_key.items():
        pts = tuple(s.rotation_point(x, r) for r in rots)
        evs = tuple(evals[(key, r)] for r in rots)
        entries.append((commits[key] if key in commits else fixed[key],
                        pts, evs))
    v = tr.challenge()
    w1 = tr.read_point()
    u = tr.challenge()
    w2 = tr.read_point()
    if tr.pos != len(tr.buf):
        return "proof has trailing bytes"
    all_points = []
    for _c, pts, _e in entries:
        for p in pts:
            if p not in all_points:
                all_points.append(p)
    f_acc = None
    e_scalar = 0
    vpow = 1
    for com, pts, evs in entries:
        wgt = vpow * _z([p for p in all_points if p not in pts], u) % R
        f_acc = g1.add(f_acc, g1.mul(com, wgt))
        e_scalar = (e_scalar + wgt * _interp_eval(pts, evs, u)) % R
        vpow = vpow * v % R
    f_acc = g1.add(f_acc, g1.neg(g1.mul(g1.G, e_scalar)))
    f_acc = g1.add(f_acc, g1.neg(g1.mul(w1, _z(all_points, u))))
    one_side = g1.add(f_acc, g1.mul(w2, u))
    # e(one_side, [1]_2) == e(w2, [tau]_2), with the set-up's tau known
    if g1.mul(w2, tau) != one_side:
        return "the opening proof does not hold against the stated set-up"
    return ""
