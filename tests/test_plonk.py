"""Proving-system tests: KZG/SHPLONK, transcripts, full prove/verify."""

import dataclasses
import os
import secrets

import numpy as np
import pytest

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.native import host
from spectre_tpu.plonk import backend as B, kzg
from spectre_tpu.plonk.constraint_system import Assignment, CircuitConfig
from spectre_tpu.plonk.domain import Domain
from spectre_tpu.plonk.keygen import keygen
from spectre_tpu.plonk.prover import prove
from spectre_tpu.plonk.srs import SRS
from spectre_tpu.plonk.transcript import Blake2bTranscript, KeccakTranscript, keccak256
from spectre_tpu.plonk.verifier import verify

from _shapes import TINY_K as K, seeded_blinding
from _shapes import tiny_circuit as _tiny_circuit


@pytest.fixture(scope="module")
def srs(tiny):
    return tiny.srs


class TestTranscript:
    def test_keccak256_vectors(self):
        # standard Keccak-256 (Ethereum) test vectors
        assert keccak256(b"").hex() == \
            "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470"
        assert keccak256(b"abc").hex() == \
            "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45"

    def test_roundtrip_and_determinism(self):
        for cls in (Blake2bTranscript, KeccakTranscript):
            tw = cls()
            pt = bn.g1_curve.mul(bn.G1_GEN, 7)
            tw.write_point(pt)
            tw.write_scalar(12345)
            c1 = tw.challenge()
            proof = tw.finalize()
            tr = cls(proof)
            assert tr.read_point() == pt
            assert tr.read_scalar() == 12345
            assert tr.challenge() == c1
            tr.assert_consumed()

    def test_infinity_point(self):
        tw = Blake2bTranscript()
        tw.write_point(None)
        tr = Blake2bTranscript(tw.finalize())
        assert tr.read_point() is None


class TestDomain:
    def test_lagrange_roundtrip(self):
        dom = Domain(5)
        vals = [secrets.randbelow(bn.R) for _ in range(32)]
        arr = B.to_arr(vals)
        back = dom.coeff_to_lagrange(dom.lagrange_to_coeff(arr))
        assert B.arr_to_ints(back) == vals

    def test_extended_roundtrip(self):
        dom = Domain(4)
        coeffs = B.to_arr([secrets.randbelow(bn.R) for _ in range(16)])
        ext = dom.coeff_to_extended(coeffs)
        back = dom.extended_to_coeff(ext)
        assert B.arr_to_ints(back[:16]) == B.arr_to_ints(coeffs)
        assert all(v == 0 for v in B.arr_to_ints(back[16:]))

    def test_lagrange_evals(self):
        dom = Domain(4)
        x = secrets.randbelow(bn.R)
        lag = dom.lagrange_evals(x, [0, 3])
        # L_i(omega^i) = 1, L_i(omega^j) = 0
        lag_at_dom = dom.lagrange_evals(dom.omega ** 3 % bn.R, [0, 3])
        assert lag_at_dom[3] == 1 and lag_at_dom[0] == 0
        # sum of all lagranges = 1
        all_lag = dom.lagrange_evals(x, range(16))
        assert sum(all_lag.values()) % bn.R == 1


class TestSHPLONK:
    def test_multipoint_roundtrip(self, srs):
        dom = Domain(K)
        n = 1 << K
        c1 = B.to_arr([secrets.randbelow(bn.R) for _ in range(n)])
        c2 = B.to_arr([secrets.randbelow(bn.R) for _ in range(n)])
        C1, C2 = kzg.commit(srs, c1), kzg.commit(srs, c2)
        x = secrets.randbelow(bn.R)
        wx = x * dom.omega % bn.R
        e1 = (host.fp_horner(host.FR, c1, x), host.fp_horner(host.FR, c1, wx))
        e2 = (host.fp_horner(host.FR, c2, x),)
        tw = Blake2bTranscript()
        for e in e1 + e2:
            tw.write_scalar(e)
        kzg.shplonk_open(srs, dom, [
            kzg.OpenEntry(c1, None, (x, wx), e1),
            kzg.OpenEntry(c2, None, (x,), e2)], tw)
        tr = Blake2bTranscript(tw.finalize())
        f1 = (tr.read_scalar(), tr.read_scalar())
        f2 = (tr.read_scalar(),)
        assert kzg.shplonk_verify(srs, [
            kzg.OpenEntry(None, C1, (x, wx), f1),
            kzg.OpenEntry(None, C2, (x,), f2)], tr)

    def test_bad_eval_rejected(self, srs):
        dom = Domain(K)
        n = 1 << K
        c1 = B.to_arr([secrets.randbelow(bn.R) for _ in range(n)])
        C1 = kzg.commit(srs, c1)
        x = secrets.randbelow(bn.R)
        bad = ((host.fp_horner(host.FR, c1, x) + 1) % bn.R,)
        tw = Blake2bTranscript()
        tw.write_scalar(bad[0])
        kzg.shplonk_open(srs, dom, [kzg.OpenEntry(c1, None, (x,), bad)], tw)
        tr = Blake2bTranscript(tw.finalize())
        f = (tr.read_scalar(),)
        assert not kzg.shplonk_verify(srs, [kzg.OpenEntry(None, C1, (x,), f)], tr)


def _open_oracle(srs, domain, entries, transcript):
    """`kzg.shplonk_open` as it stood before it combined the entries a point
    set: every entry transformed, its remainder put on the domain and divided
    by its Z_S one at a time, then a second walk over the entries for L.
    Kept here as the oracle of the grouped form, on `CpuBackend`."""
    bk = B.get_backend()
    n = domain.n
    omegas = bk.powers(domain.omega, n)
    v = transcript.challenge()
    all_points = []
    for e in entries:
        for p in e.points:
            if p not in all_points:
                all_points.append(p)

    h_evals = B.zeros(n)
    vk = 1
    cache = []
    for e in entries:
        z_s = None
        for s in e.points:
            term = bk.sub(omegas, B.to_arr([s] * n))
            z_s = term if z_s is None else bk.mul(z_s, term)
        padded = np.zeros((n, 4), dtype=np.uint64)
        padded[:e.coeffs.shape[0]] = e.coeffs
        p_evals = domain.coeff_to_lagrange(padded, bk)
        r_coeffs = kzg._interp(e.points, e.evals)
        r_evals = B.to_arr([r_coeffs[-1]] * n)
        for c in reversed(r_coeffs[:-1]):
            r_evals = bk.add(bk.mul(r_evals, omegas), B.to_arr([c] * n))
        term = bk.mul(bk.sub(p_evals, r_evals), bk.inv(z_s))
        h_evals = bk.add(h_evals, bk.scale(term, vk))
        cache.append((p_evals, r_coeffs))
        vk = vk * v % bn.R
    h_coeffs = domain.lagrange_to_coeff(h_evals, bk)
    w1 = kzg.commit(srs, h_coeffs, bk)
    transcript.write_point(w1)
    u = transcript.challenge()

    l_evals = B.zeros(n)
    vk = 1
    for e, (p_evals, r_coeffs) in zip(entries, cache):
        z_rest = kzg._z_eval([p for p in all_points if p not in e.points], u)
        r_u = 0
        for c in reversed(r_coeffs):
            r_u = (r_u * u + c) % bn.R
        term = bk.sub(p_evals, B.to_arr([r_u] * n))
        l_evals = bk.add(l_evals, bk.scale(term, vk * z_rest % bn.R))
        vk = vk * v % bn.R
    l_evals = bk.sub(l_evals, bk.scale(
        domain.coeff_to_lagrange(h_coeffs, bk), kzg._z_eval(all_points, u)))
    denom_inv = bk.inv(bk.sub(omegas, B.to_arr([u] * n)))
    w2_coeffs = domain.lagrange_to_coeff(bk.mul(l_evals, denom_inv), bk)
    w2 = kzg.commit(srs, w2_coeffs, bk)
    transcript.write_point(w2)


class _Forced:
    """A Blake2b transcript whose first challenges are the ones given (v,
    then u; None: the transcript's own), everything else its own."""

    def __init__(self, *forced):
        self.tr, self.forced, self.points = Blake2bTranscript(), list(forced), []

    def challenge(self):
        own = self.tr.challenge()
        given = self.forced.pop(0) if self.forced else None
        return own if given is None else given

    def write_point(self, pt):
        self.points.append(pt)
        self.tr.write_point(pt)


# the rotation families of `keygen.query_plan`, as a committee prove's 231
# entries share them (LAST and `back` stand for whatever rows they name)
_ROTATION_FAMILIES = (
    (0, 1, 2, 3), (0,), (0, -1), (0, 1), (0, 1, -5),
    (0, -2, -7, -15, -16), (0, -1, -2, -3, -4), (0, -9))


def _open_case(case, dom):
    """(entries, v, u) of one case of the grouped open; None leaves a
    challenge to the transcript. Polynomials and x are drawn anew a case."""
    n = dom.n
    rng = secrets.SystemRandom()
    x = rng.randrange(bn.R)

    def poly(rows=n):
        return B.to_arr([rng.randrange(bn.R) for _ in range(rows)])

    def entry(coeffs, rots):
        pts = tuple(x * pow(dom.omega, r, bn.R) % bn.R for r in rots)
        return kzg.OpenEntry(coeffs, None, pts, tuple(
            host.fp_horner(host.FR, coeffs, p) for p in pts))

    if case == "one_set":
        return [entry(poly(), (0, 1)) for _ in range(3)], None, None
    if case == "eight_families_mixed":      # every family twice, interleaved
        fams = _ROTATION_FAMILIES + _ROTATION_FAMILIES[::-1]
        return [entry(poly(), f) for f in fams], None, None
    if case == "five_point_set":
        return [entry(poly(), _ROTATION_FAMILIES[5])], None, None
    if case == "shorter_than_n":            # a chunk of h; a constant; n rows
        return [entry(poly(n // 2), (0,)), entry(poly(1), (0, -1)),
                entry(poly(), (0,))], None, None
    if case == "equal_polynomials":         # the same column under two keys
        p = poly()
        return [entry(p, (0, 1)), entry(p, (0, 1)), entry(p, (0,))], None, None
    if case == "set_first_and_last":
        return [entry(poly(), (0, 1)), entry(poly(), (0,)),
                entry(poly(), (0, -1)), entry(poly(), (0, 1))], None, None
    mixed = [entry(poly(), f) for f in _ROTATION_FAMILIES[:4] * 2]
    if case == "v_zero":        # every weight but the first vanishes
        return mixed, 0, None
    if case == "v_one":         # equal weights: a set's entries just add
        return mixed, 1, None
    if case == "u_zero":
        return mixed, None, 0
    if case == "u_one":         # u = omega^0: X - u vanishes on the domain
        return mixed, None, 1
    raise ValueError(case)


class TestOpenGroupedBySet:
    """`shplonk_open`, which combines the entries a point set before it
    touches the domain, against the entry-at-a-time form it replaced: W1, W2
    and the transcript's state, byte for byte on `CpuBackend`."""

    @pytest.mark.parametrize("case", [
        "one_set", "eight_families_mixed", "five_point_set", "shorter_than_n",
        "equal_polynomials", "set_first_and_last", "v_zero", "v_one",
        "u_zero", "u_one"])
    def test_equals_entry_at_a_time(self, srs, case):
        from spectre_tpu.observability import tracing

        dom = Domain(K)
        entries, v, u = _open_case(case, dom)
        want, got = _Forced(v, u), _Forced(v, u)
        _open_oracle(srs, dom, entries, want)
        with tracing.trace("open") as tr:
            kzg.shplonk_open(srs, dom, entries, got)
        assert got.points == want.points and len(got.points) == 2
        assert got.tr.finalize() == want.tr.finalize()
        assert got.tr.challenge() == want.tr.challenge()
        # the mechanism: the domain is touched once a set, not once an entry
        spans, todo = [], [tr.root]
        while todo:
            spans.append(todo.pop())
            todo += spans[-1].children
        h_poly, = [s for s in spans if s.name == "multiopen/h_poly"]
        n_sets = len({e.points for e in entries})
        assert h_poly.meta["entries"] == len(entries)
        assert h_poly.meta["sets"] == n_sets
        assert sum(s.name == "multiopen/h_poly/remainder"
                   for s in spans) == n_sets
        assert sum(s.name == "multiopen/h_poly/combine" for s in spans) == 1


def _accumulate_oracle(srs, entries, transcript):
    """`kzg.shplonk_accumulate` as it stood before it became one native MSM:
    one double-and-add of `CurveGroup` an opened polynomial. Kept here as
    the oracle of the MSM path."""
    g1 = bn.g1_curve
    v = transcript.challenge()
    w1 = transcript.read_point()
    u = transcript.challenge()
    w2 = transcript.read_point()
    all_points = []
    for e in entries:
        for p in e.points:
            if p not in all_points:
                all_points.append(p)
    f_acc = None
    e_scalar = 0
    vk = 1
    for e in entries:
        z_rest = kzg._z_eval([p for p in all_points if p not in e.points], u)
        r_coeffs = kzg._interp(e.points, e.evals)
        r_u = 0
        for c in reversed(r_coeffs):
            r_u = (r_u * u + c) % bn.R
        w = vk * z_rest % bn.R
        f_acc = g1.add(f_acc, g1.mul(e.commitment, w))
        e_scalar = (e_scalar + w * r_u) % bn.R
        vk = vk * v % bn.R
    z_t_u = kzg._z_eval(all_points, u)
    f_acc = g1.add(f_acc, g1.neg(g1.mul(bn.G1_GEN, e_scalar)))
    f_acc = g1.add(f_acc, g1.neg(g1.mul(w1, z_t_u)))
    return w2, g1.add(f_acc, g1.mul(w2, u))


class _FourValues:
    """The transcript as `shplonk_accumulate` sees it: two challenges and
    two points, handed out in the order they are asked for."""

    def __init__(self, v, w1, u, w2):
        self._challenges, self._points = [v, u], [w1, w2]

    def challenge(self):
        return self._challenges.pop(0)

    def read_point(self):
        return self._points.pop(0)


@pytest.fixture(scope="module")
def accumulate_inputs(tiny, tiny_cpu_proof):
    """What the verifier hands `shplonk_accumulate` for a real proof of the
    tiny gate + lookup + copy circuit: (entries, v, w1, u, w2)."""
    seen = {}
    real = kzg.shplonk_accumulate

    class Tap:
        def __init__(self, tr):
            self.tr, self.values = tr, []

        def challenge(self):
            self.values.append(self.tr.challenge())
            return self.values[-1]

        def read_point(self):
            self.values.append(self.tr.read_point())
            return self.values[-1]

    def spy(srs, entries, tr):
        tap = Tap(tr)
        out = real(srs, entries, tap)
        seen["entries"], seen["values"] = list(entries), tap.values
        return out

    mp = pytest.MonkeyPatch()
    try:
        mp.setattr(kzg, "shplonk_accumulate", spy)
        assert verify(tiny.pk.vk, tiny.srs, tiny.instances, tiny_cpu_proof)
    finally:
        mp.undo()
    return (seen["entries"], *seen["values"])


def _accumulate_case(case, inputs):
    """(entries, v, w1, u, w2) of one edge of the native MSM. With v = 1 two
    entries opened at the same points carry the same weight, so their
    commitments meet in one bucket of every window."""
    entries, v, w1, u, w2 = inputs
    first = entries[0]
    minus_first = dataclasses.replace(
        first, commitment=bn.g1_curve.neg(first.commitment))
    if case == "real_proof":
        return entries, v, w1, u, w2
    if case == "none_commitment":       # an all-zero fixed or selector column
        return [first, dataclasses.replace(entries[1], commitment=None),
                *entries[2:]], v, w1, u, w2
    if case == "same_commitment_two_keys":      # the bucket doubles
        return [first, *entries], 1, w1, u, w2
    if case == "p_and_minus_p":                 # the bucket empties again
        return [minus_first, *entries], 1, w1, u, w2
    if case == "zero_weight":           # v = 0: every weight but the first
        return entries, 0, w1, u, w2
    if case == "wide":          # 64 pairs and over: the kernel's window is 8
        return [dataclasses.replace(e, commitment=bn.g1_curve.mul(bn.G1_GEN, 3 + i))
                for i, e in enumerate(entries * (70 // len(entries) + 1))], \
            v, w1, u, w2
    if case == "all_cancelling":        # P - P, r - r, no W1, no W2: identity
        return [first, dataclasses.replace(
            minus_first, evals=tuple(-x % bn.R for x in first.evals))], \
            1, None, u, None
    raise ValueError(case)


class TestAccumulateNativeMsm:
    """`shplonk_accumulate`'s one native MSM against the Python loop it
    replaced, on what a real proof hands it and on each edge the native
    kernel has to carry."""

    @pytest.mark.parametrize("case", [
        "real_proof", "none_commitment", "same_commitment_two_keys",
        "p_and_minus_p", "zero_weight", "wide", "all_cancelling"])
    def test_equals_python_loop(self, srs, accumulate_inputs, case):
        entries, *values = _accumulate_case(case, accumulate_inputs)
        want = _accumulate_oracle(srs, entries, _FourValues(*values))
        got = kzg.shplonk_accumulate(srs, entries, _FourValues(*values))
        assert got == want
        tau_side, one_side = got
        assert (one_side is None) == (case == "all_cancelling")
        if one_side is not None:
            assert all(isinstance(c, bn.Fq) for c in one_side)
            assert bn.g1_curve.is_on_curve(one_side)

    def test_verifier_never_asks_for_the_backend(self, tiny, tiny_cpu_proof,
                                                 monkeypatch):
        """The verifier guards the served proof against the device: it may
        not commit through whatever backend the service is configured with."""
        def refuse(*a, **kw):
            raise RuntimeError("the verifier asked for the backend")

        monkeypatch.setattr(B, "get_backend", refuse)
        assert verify(tiny.pk.vk, tiny.srs, tiny.instances, tiny_cpu_proof)
        assert not verify(tiny.pk.vk, tiny.srs, [[tiny.out + 1]],
                          tiny_cpu_proof)


class TestProveVerify:
    def test_end_to_end(self, tiny):
        pk, srs, out = tiny.pk, tiny.srs, tiny.out
        proof = prove(pk, srs, tiny.asg)
        assert verify(pk.vk, srs, [[out]], proof)
        assert not verify(pk.vk, srs, [[out + 1]], proof)

    def test_malformed_proof_bytes_reject_not_raise(self, tiny,
                                                    tiny_cpu_proof):
        """Untrusted proof bytes must yield a boolean reject, never an
        exception: truncated, trailing-garbage, and non-canonical-scalar
        proofs all return False."""
        pk, srs, out, proof = tiny.pk, tiny.srs, tiny.out, tiny_cpu_proof
        assert not verify(pk.vk, srs, [[out]], proof + b"\x00" * 7)
        assert not verify(pk.vk, srs, [[out]], proof[:-5])
        assert not verify(pk.vk, srs, [[out]], b"")
        assert not verify(pk.vk, srs, [[out]], proof[:64] + b"\xff" * (len(proof) - 64))

    def test_multi_advice_columns(self, srs):
        # two gate columns + wider permutation (multiple chunks exercised)
        cfg = CircuitConfig(k=K, num_advice=2, num_lookup_advice=1, num_fixed=1,
                            lookup_bits=4)
        n = cfg.n
        advice = [[0] * n, [0] * n]
        selectors = [[0] * n, [0] * n]
        # col0: 2 + 3*4 = 14 ; col1: 14 + 14*1 = 28, cross-column copy
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
        proof = prove(pk, srs, asg)
        assert verify(pk.vk, srs, [[28]], proof)

    def test_invalid_gate_witness_rejected(self, srs):
        cfg = CircuitConfig(k=K, num_advice=1, num_lookup_advice=1, num_fixed=1,
                            lookup_bits=4)
        advice, lookup, fixed, selectors, copies, out = _tiny_circuit(cfg)
        advice[0][2] = 999  # breaks the gate (x + x*y != out)
        pk = keygen(srs, cfg, fixed, selectors, copies)
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
        # the prover refuses: quotient division is inexact for a bad witness
        with pytest.raises(AssertionError, match="witness violates"):
            prove(pk, srs, asg)

    def test_out_of_range_lookup_rejected_at_prove(self, srs):
        cfg = CircuitConfig(k=K, num_advice=1, num_lookup_advice=1, num_fixed=1,
                            lookup_bits=4)
        advice, lookup, fixed, selectors, copies, out = _tiny_circuit(cfg)
        lookup[0][1] = 99999  # not in [0, 16)
        pk = keygen(srs, cfg, fixed, selectors, copies)
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
        with pytest.raises(AssertionError, match="not in table"):
            prove(pk, srs, asg)

    def test_copy_violation_rejected_at_prove(self, srs):
        cfg = CircuitConfig(k=K, num_advice=1, num_lookup_advice=1, num_fixed=1,
                            lookup_bits=4)
        advice, lookup, fixed, selectors, copies, out = _tiny_circuit(cfg)
        advice[0][4] = 6  # violates the constant-5 copy constraint
        pk = keygen(srs, cfg, fixed, selectors, copies)
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
        with pytest.raises(AssertionError, match="permutation product"):
            prove(pk, srs, asg)

    def test_proof_is_zk_randomized(self, tiny):
        pk, srs, out = tiny.pk, tiny.srs, tiny.out
        p1 = prove(pk, srs, tiny.asg)
        p2 = prove(pk, srs, tiny.asg)
        assert p1 != p2  # blinding rows differ
        assert verify(pk.vk, srs, [[out]], p1) and verify(pk.vk, srs, [[out]], p2)


class TestLookupBoundarySoundness:
    """Round-1 ADVICE high finding: the lookup grand product needs the
    l_last*(lz^2 - lz) boundary constraint, or a prover who sets A'=T'=table
    can 'look up' arbitrary out-of-range advice (the permutation relation is
    never anchored). These keep that hole closed."""

    def test_boundary_term_present_in_expressions(self):
        from spectre_tpu.plonk.expressions import ScalarCtx, all_expressions

        class _Zeros(dict):
            def __missing__(self, key):
                return 0

        cfg = CircuitConfig(k=K, num_advice=1, num_lookup_advice=1,
                            num_fixed=1, lookup_bits=4)
        evals = _Zeros()
        evals[(("lz", 0), 0)] = 2  # lz(last) not in {0,1}
        # at the l_last row: l0=0, act = 1 - llast - lblind = 0 — every other
        # constraint vanishes on the all-zero evals, so any nonzero entry IS
        # the boundary term
        ctx = ScalarCtx(cfg, evals, l0=0, llast=1, lblind=0, x=0)
        exprs = all_expressions(cfg, ctx, beta=1, gamma=1)
        assert any(e % bn.R != 0 for e in exprs), \
            "lookup boundary constraint missing: lz(last)=2 satisfied everything"

    def test_forged_lookup_rejected(self, srs, monkeypatch):
        """Replays the round-1 PoC: permuted columns = (table, table), advice
        contains 99999999, honest-prover asserts bypassed. The boundary
        constraint must now make the quotient division inexact."""
        from spectre_tpu.plonk import prover as prover_mod

        cfg = CircuitConfig(k=K, num_advice=1, num_lookup_advice=1,
                            num_fixed=1, lookup_bits=4)
        advice, lookup, fixed, selectors, copies, out = _tiny_circuit(cfg)
        lookup[0][1] = 99999999  # far outside the 4-bit table

        def evil_permute(cfg_, a_vals, t_vals):
            return list(t_vals), list(t_vals)  # A' = T' = table

        def evil_grand_product(bk, n, u, a_v, pa_v, pt_v, t_v, beta, gamma):
            num = bk.mul(bk.add(B.to_arr(a_v), B.to_arr([beta] * n)),
                         bk.add(B.to_arr(t_v), B.to_arr([gamma] * n)))
            den = bk.mul(bk.add(B.to_arr(pa_v), B.to_arr([beta] * n)),
                         bk.add(B.to_arr(pt_v), B.to_arr([gamma] * n)))
            ratio = B.arr_to_ints(bk.mul(num, bk.inv(den)))
            for i in range(u, n):
                ratio[i] = 1
            prefix = B.arr_to_ints(bk.prefix_prod(B.to_arr(ratio)))
            return [1] + prefix[:-1]  # telescope assert skipped

        monkeypatch.setattr(prover_mod, "permute_lookup", evil_permute)
        monkeypatch.setattr(prover_mod, "lookup_grand_product",
                            evil_grand_product)
        pk = keygen(srs, cfg, fixed, selectors, copies)
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
        with pytest.raises(AssertionError, match="witness violates"):
            prove(pk, srs, asg)


class TestMockProver:
    def test_satisfied(self):
        from spectre_tpu.plonk.mock import mock_prove
        cfg = CircuitConfig(k=7, num_advice=1, num_lookup_advice=1, num_fixed=1,
                            lookup_bits=4)
        advice, lookup, fixed, selectors, copies, out = _tiny_circuit(cfg)
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
        assert mock_prove(cfg, asg)

    def test_reports_gate_violation(self):
        from spectre_tpu.plonk.mock import mock_prove
        cfg = CircuitConfig(k=7, num_advice=1, num_lookup_advice=1, num_fixed=1,
                            lookup_bits=4)
        advice, lookup, fixed, selectors, copies, out = _tiny_circuit(cfg)
        advice[0][2] = 12  # gate broken
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
        with pytest.raises(AssertionError, match="constraint #0 violated at row 0"):
            mock_prove(cfg, asg)

    def test_reports_copy_violation(self):
        from spectre_tpu.plonk.mock import mock_prove
        cfg = CircuitConfig(k=7, num_advice=1, num_lookup_advice=1, num_fixed=1,
                            lookup_bits=4)
        advice, lookup, fixed, selectors, copies, out = _tiny_circuit(cfg)
        advice[0][4] = 99
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
        with pytest.raises(AssertionError, match="copy constraint violated"):
            mock_prove(cfg, asg)

    def test_reports_lookup_violation(self):
        from spectre_tpu.plonk.mock import mock_prove
        cfg = CircuitConfig(k=7, num_advice=1, num_lookup_advice=1, num_fixed=1,
                            lookup_bits=4)
        advice, lookup, fixed, selectors, copies, out = _tiny_circuit(cfg)
        lookup[0][9] = 1 << 20
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
        with pytest.raises(AssertionError, match="not in table"):
            mock_prove(cfg, asg)


class TestDeviceQuotient:
    """quotient_device.py: device-resident evaluation of the whole
    constraint identity must match the host-orchestrated quotient EXACTLY
    (same u64 coefficient arrays) — compared in-situ during a real prove
    via a _quotient_host wrapper, so all inputs (blinds, grand products,
    challenges) are the production ones."""

    def _check(self, build_fn, k, lookup_bits, srs_k):
        import spectre_tpu.plonk.prover as P
        from spectre_tpu.builder.context import Context
        from spectre_tpu.plonk.quotient_device import compute_quotient

        ctx = Context()
        build_fn(ctx)
        cfg = ctx.auto_config(k=k, lookup_bits=lookup_bits)
        asg = ctx.assignment(cfg)
        srs_ = SRS.unsafe_setup(srs_k)
        bk = B.get_backend("cpu")
        pk = keygen(srs_, cfg, asg.fixed, asg.selectors, asg.copies, bk)
        orig_q = P._quotient_host
        res = {}

        def wrapped(cfg_, dom_, bk_, pk_, polys_, beta, gamma, y):
            h_host = orig_q(cfg_, dom_, bk_, pk_, polys_, beta, gamma, y)

            def fetch(key):
                kind, j = key
                if key in polys_:
                    return polys_[key]
                if kind == "shk":
                    return pk_.sha_k_poly
                return {"q": pk_.selector_polys, "fix": pk_.fixed_polys,
                        "sig": pk_.sigma_polys, "tab": pk_.table_polys,
                        "shq": pk_.sha_selector_polys}[kind][j]

            h_dev = compute_quotient(cfg_, dom_, fetch, beta, gamma, y)
            res["equal"] = bool((h_host == h_dev).all())
            return h_host

        P._quotient_host = wrapped
        try:
            proof = P.prove(pk, srs_, asg, bk)
        finally:
            P._quotient_host = orig_q
        assert verify(pk.vk, srs_, asg.instances, proof)
        assert res["equal"], "device quotient != host quotient"

    def test_gate_lookup_circuit(self):
        from spectre_tpu.builder import RangeChip

        def build(ctx):
            rng = RangeChip(lookup_bits=4)
            g = rng.gate
            a = ctx.load_witness(5)
            b = ctx.load_witness(9)
            c = g.mul(ctx, a, b)
            rng.range_check(ctx, a, 4)
            ctx.expose_public(c)

        self._check(build, k=5, lookup_bits=4, srs_k=7)

    @pytest.mark.skipif(not os.environ.get("RUN_SLOW"),
                        reason="device NTT compiles (set RUN_SLOW=1)")
    def test_wide_sha_circuit(self):
        """Region expressions, negative rotations, ROT_LAST, inst."""
        from spectre_tpu.builder import GateChip
        from spectre_tpu.builder.sha256_wide_chip import Sha256WideChip
        from spectre_tpu.gadgets import ssz_merkle as M

        def build(ctx):
            sha = Sha256WideChip(GateChip())
            cells = M.load_bytes_checked(ctx, sha, b"dq")
            digest = sha.digest_bytes(ctx, cells)
            ctx.expose_public(digest[0].cell)

        self._check(build, k=9, lookup_bits=5, srs_k=11)


class TestKeccakTranscriptPath:
    """The EVM-oriented transcript (Keccak-256) through full prove/verify —
    the reference's gen_evm_proof path uses exactly this hash for challenges."""

    def test_prove_verify_keccak(self, tiny):
        pk, srs, out = tiny.pk, tiny.srs, tiny.out
        proof = prove(pk, srs, tiny.asg, transcript=KeccakTranscript())
        assert verify(pk.vk, srs, [[out]], proof, transcript_cls=KeccakTranscript)
        # a keccak proof must NOT verify under the blake2b transcript
        assert not verify(pk.vk, srs, [[out]], proof)


class TestBackendByteEquality:
    """VERDICT r3 item 4: the SAME proof bytes must come out of CpuBackend
    and TpuBackend when the ZK blinding is seeded identically — the backends
    differ only in WHERE the math runs, never in WHAT they compute. Default
    tier (shapes shared with TestProveVerify for a warm compile cache)."""

    _seeded_rng = staticmethod(seeded_blinding)

    @pytest.mark.skipif(not os.environ.get("SPECTRE_BYTEEQ_FULL"),
                        reason="this box's XLA CPU LLVM segfaults under "
                               "repeated prove compile churn; opt in with "
                               "SPECTRE_BYTEEQ_FULL=1 (real-device tier)")
    def test_msm_mode_proof_bytes_identical(self, srs, monkeypatch):
        """Full-prove tier of the gate: every MSM mode must produce
        BYTE-IDENTICAL proofs to the vanilla path under seeded blinding."""
        cfg = CircuitConfig(k=K, num_advice=1, num_lookup_advice=1,
                            num_fixed=1, lookup_bits=4)
        advice, lookup, fixed, selectors, copies, out = _tiny_circuit(cfg)
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
        bk = B.get_backend("tpu")
        monkeypatch.setenv("SPECTRE_MSM_MODE", "vanilla")
        pk = keygen(srs, cfg, fixed, selectors, copies, bk)
        base = prove(pk, srs, asg, bk, blinding_rng=self._seeded_rng(7))
        assert verify(pk.vk, srs, [[out]], base)
        for mode in ("glv", "glv+signed", "fixed"):
            monkeypatch.setenv("SPECTRE_MSM_MODE", mode)
            p = prove(pk, srs, asg, bk, blinding_rng=self._seeded_rng(7))
            assert p == base, \
                f"SPECTRE_MSM_MODE={mode} diverged from vanilla proof bytes"

    def test_seeded_blinding_is_deterministic_and_fresh_is_not(self, tiny):
        pk, srs, asg, out = tiny.pk, tiny.srs, tiny.asg, tiny.out
        p1 = prove(pk, srs, asg, blinding_rng=self._seeded_rng(1))
        p2 = prove(pk, srs, asg, blinding_rng=self._seeded_rng(1))
        assert p1 == p2
        # default blinding: fresh system randomness -> different bytes
        p3 = prove(pk, srs, asg)
        assert p3 != p1 and verify(pk.vk, srs, [[out]], p3)


class TestQuotientCacheEviction:
    """BASELINE.md claims the byte-budgeted extended-array LRU is
    'regression-pinned under forced eviction' — pin it for real (ADVICE r5):
    a prove under SPECTRE_QUOTIENT_CACHE_MB=1 must produce BYTE-EQUAL output
    to the default-budget prove with the same seeded blinding (eviction
    costs recompute time, never correctness), and the thrash warning must
    fire when a working set recomputes past the threshold."""

    def test_forced_eviction_proof_byte_equal(self, monkeypatch):
        # k=11: ~25 distinct extended arrays of 256KB each + rolls, so a
        # 1 MB budget GUARANTEES eviction + recomputes during the quotient
        k = 11
        srs11 = SRS.unsafe_setup(k)
        cfg = CircuitConfig(k=k, num_advice=1, num_lookup_advice=1,
                            num_fixed=1, lookup_bits=4)
        advice, lookup, fixed, selectors, copies, out = _tiny_circuit(cfg)
        asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
        pk = keygen(srs11, cfg, fixed, selectors, copies)

        def seeded():
            import random
            r = random.Random(0xFEED)
            return lambda: r.randrange(bn.R)

        monkeypatch.delenv("SPECTRE_QUOTIENT_CACHE_MB", raising=False)
        p_default = prove(pk, srs11, asg, blinding_rng=seeded())
        monkeypatch.setenv("SPECTRE_QUOTIENT_CACHE_MB", "1")
        p_evicting = prove(pk, srs11, asg, blinding_rng=seeded())
        assert p_default == p_evicting, \
            "LRU eviction changed proof bytes (recompute path diverges)"
        assert verify(pk.vk, srs11, [[out]], p_evicting)

    def test_thrash_warning_fires_once(self, monkeypatch, capsys):
        from spectre_tpu.plonk.prover import _BudgetedExtLRU
        arr = np.zeros((64, 4), dtype=np.uint64)   # 2KB
        lru = _BudgetedExtLRU(budget_bytes=3 * arr.nbytes)
        monkeypatch.setattr(_BudgetedExtLRU, "THRASH_WARN_THRESHOLD", 4)
        for round_ in range(3):
            for key in ("a", "b", "c", "d", "e"):   # 5 keys, 3 fit
                if lru.get(key) is None:
                    lru.put(key, arr)
        assert lru.recompute_count >= 4
        err = capsys.readouterr().err
        assert err.count("cache thrashing") == 1


class TestArrayCtxExtContract:
    """_ArrayCtx._ext is 'a mapping or callable cache' — the base class must
    honor BOTH (ADVICE r5: _quotient_host now passes a callable)."""

    def test_var_accepts_mapping_and_callable(self):
        from spectre_tpu.plonk.prover import _ArrayCtx

        class Bare:
            pass

        ctx = Bare()
        ctx._ext = {("adv", 0): "mapped"}
        assert _ArrayCtx.var(ctx, ("adv", 0), 0) == "mapped"
        ctx._ext = lambda key: ("called", key)
        assert _ArrayCtx.var(ctx, ("adv", 0), 0) == ("called", ("adv", 0))
