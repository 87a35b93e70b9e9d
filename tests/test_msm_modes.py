"""GLV decomposition, signed-digit recoding, and MSM mode equivalence.

The contract every mode must honor: identical group element out (the
commitment byte-equality gate rides on this), only the work shape differs.
"""

import functools
import secrets

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.ops import ec, glv, limbs as L, msm as MSM
from spectre_tpu.plonk import backend as B, kzg
from spectre_tpu.utils.health import HEALTH

from _shapes import (MSM_CASES, MSM_N, MSM_N_OTHER_MODES, MSM_WINDOWS,
                     check_msm_case, encode_msm, kernel_programs, msm_base,
                     msm_case)


def _edge_scalars():
    lam = glv.lam()
    return [0, 1, 2, bn.R - 1, bn.R - 2, lam, bn.R - lam, lam - 1,
            (bn.R - 1) // 2, 1 << 128, (1 << 253) - 1]


class TestGLVDecompose:
    def test_recomposes_mod_r(self):
        lam = glv.lam()
        for k in _edge_scalars() + [secrets.randbelow(bn.R)
                                    for _ in range(64)]:
            k1, k2 = glv.decompose(k)
            assert (k1 + k2 * lam) % bn.R == k % bn.R, k

    def test_half_scalars_bounded(self):
        bound = 1 << glv.glv_bits()
        assert glv.glv_bits() <= 16 * glv.HALF_LIMBS
        for k in _edge_scalars() + [secrets.randbelow(bn.R)
                                    for _ in range(64)]:
            k1, k2 = glv.decompose(k)
            assert -bound < k1 < bound and -bound < k2 < bound, k

    def test_batch_matches_scalar_path(self):
        ks = _edge_scalars() + [secrets.randbelow(bn.R) for _ in range(16)]
        a1, a2, n1, n2 = glv.decompose_batch(ks)
        for i, k in enumerate(ks):
            k1, k2 = glv.decompose(k)
            assert bool(n1[i]) == (k1 < 0) and bool(n2[i]) == (k2 < 0), k
            assert sum(int(a1[i, j]) << (16 * j)
                       for j in range(glv.HALF_LIMBS)) == abs(k1)
            assert sum(int(a2[i, j]) << (16 * j)
                       for j in range(glv.HALF_LIMBS)) == abs(k2)

    def test_sign_flip_cases(self):
        """Full-size scalars hit every half-scalar sign combination (small
        scalars decompose trivially to k1=k, k2=0 — the generator must span
        the whole of Fr)."""
        seen = set()
        g = bn.FR_GENERATOR
        for k in range(1, 256):
            k1, k2 = glv.decompose(pow(g, k, bn.R))
            seen.add((k1 < 0, k2 < 0))
            if len(seen) == 4:
                break
        assert len(seen) == 4, f"only sign patterns {seen} exercised"

    def test_endo_matches_lambda_mul(self):
        pts = [bn.g1_curve.mul(bn.G1_GEN, 3 * i + 2) for i in range(4)]
        pts.append(None)     # phi fixes infinity
        got = ec.decode_points(jax.jit(ec.endo)(ec.encode_points(pts)))
        lam = glv.lam()
        for p, g in zip(pts, got):
            want = bn.g1_curve.mul(p, lam) if p is not None else None
            want = None if want is None else (int(want[0]), int(want[1]))
            assert g == want


class TestGLVDeviceDecompose:
    """The traced on-device Babai rounding (glv.decompose_device) must be
    BIT-EXACT against the host decompose_batch — magnitudes AND signs —
    or the device-decomposed modes silently diverge from the mesh runners,
    which decompose on the host."""

    def _device_vs_host(self, ks):
        limbs = np.asarray(L.ints_to_limbs16(ks), dtype=np.uint32)
        a1h, a2h, n1h, n2h = glv.decompose_batch(ks)
        a1d, a2d, n1d, n2d = (np.asarray(v) for v in
                              glv.decompose_device(jnp.asarray(limbs)))
        assert np.array_equal(a1d, a1h) and np.array_equal(a2d, a2h)
        assert np.array_equal(n1d.astype(bool), np.asarray(n1h))
        assert np.array_equal(n2d.astype(bool), np.asarray(n2h))

    def test_boundary_scalars(self):
        self._device_vs_host(_edge_scalars())

    def test_randomized_sweep(self):
        self._device_vs_host([secrets.randbelow(bn.R) for _ in range(64)])

    def test_babai_rounding_edges(self):
        """Scalars engineered near the floor-division rounding boundary:
        the device path computes c_i = floor((2k*b + R) / 2R) by exact
        Barrett division, so k values that put 2k*b + R within a few
        multiples of R of a 2R boundary are the worst case for an
        off-by-one (these are exactly where an inexact reciprocal
        approximation would break)."""
        (a1, b1), (a2, b2) = glv._constants()[2]
        edges = []
        for bb in (b2, -b1):
            for q in (1, 2, (1 << 125) // 7, (1 << 126) // 3):
                # 2k*bb + R ~= q*2R  ->  k ~= (2q - 1)*R / (2*bb)
                k0 = ((2 * q - 1) * bn.R) // (2 * bb)
                for d in (-2, -1, 0, 1, 2):
                    k = (k0 + d) % bn.R
                    edges.append(k)
        self._device_vs_host(edges)

    def test_device_split_feeds_msm_paths(self):
        """_glv_scalars_device output recomposes to k mod R through the
        lambda relation (the property every GLV MSM mode relies on)."""
        lam = glv.lam()
        ks = _edge_scalars()[:6] + [secrets.randbelow(bn.R)
                                    for _ in range(4)]
        sc2, neg = MSM._glv_scalars_device(
            jnp.asarray(np.asarray(L.ints_to_limbs16(ks),
                                   dtype=np.uint32)))
        sc2, neg = np.asarray(sc2), np.asarray(neg)
        n = len(ks)
        for i, k in enumerate(ks):
            k1 = sum(int(sc2[i, j]) << (16 * j)
                     for j in range(glv.HALF_LIMBS))
            k2 = sum(int(sc2[n + i, j]) << (16 * j)
                     for j in range(glv.HALF_LIMBS))
            if neg[i]:
                k1 = -k1
            if neg[n + i]:
                k2 = -k2
            assert (k1 + k2 * lam) % bn.R == k % bn.R, k


class TestSignedDigits:
    @pytest.mark.parametrize("c", [4, 8, 11, 13])
    def test_roundtrip_and_range(self, c):
        nbits = glv.glv_bits()
        nwin = (nbits + c) // c
        vals = [0, 1, (1 << nbits) - 1, 1 << (c - 1), (1 << c) - 1] + \
            [secrets.randbelow(1 << nbits) for _ in range(16)]
        limbs = np.zeros((len(vals), glv.HALF_LIMBS), np.uint32)
        for i, v in enumerate(vals):
            for j in range(glv.HALF_LIMBS):
                limbs[i, j] = (v >> (16 * j)) & 0xFFFF
        digs = np.asarray(MSM.signed_digit_stream(jnp.asarray(limbs), c, nwin))
        half = 1 << (c - 1)
        assert digs.min() >= -half + 1 and digs.max() <= half
        for i, v in enumerate(vals):
            back = sum(int(digs[w, i]) << (c * w) for w in range(nwin))
            assert back == v, (c, v)

    def test_matches_unsigned_stream(self):
        """The signed stream is a recoding OF the unsigned digit stream:
        summing both must agree (round-trip through the same scalar)."""
        import jax
        c, nbits = 10, glv.glv_bits()
        nwin_u = (nbits + c - 1) // c
        nwin_s = (nbits + c) // c
        k = secrets.randbelow(1 << nbits)
        limbs = np.zeros((1, glv.HALF_LIMBS), np.uint32)
        for j in range(glv.HALF_LIMBS):
            limbs[0, j] = (k >> (16 * j)) & 0xFFFF
        arr = jnp.asarray(limbs)
        from spectre_tpu.ops import field_ops as F
        unsigned = [int(np.asarray(
            jax.jit(lambda a, w=w: F.limb_digits(a, w, c))(arr))[0])
            for w in range(nwin_u)]
        signed = np.asarray(MSM.signed_digit_stream(arr, c, nwin_s))[:, 0]
        assert sum(d << (c * w) for w, d in enumerate(unsigned)) == \
            sum(int(d) << (c * w) for w, d in enumerate(signed)) == k


# The modes whose kernels this file compiles. The default mode's (vanilla)
# cases are tests/test_device_prove.py::TestDefaultMsm's, beside the tiny
# device prove that commits with the same program: under `--dist loadfile`
# a file is one worker's, and the kernel programs are shared out so that no
# file waits for all of them.
OTHER_MODES = ("glv", "glv+signed", "fixed")


class TestMSMModes:
    """Every mode but the default against the host curve's MSM at ONE shape
    a mode (MSM_N_OTHER_MODES points, the mode's own window there): the
    edge inputs are rows of that shape, so a mode compiles its kernel once
    for all of them."""

    @pytest.mark.parametrize("case", MSM_CASES)
    @pytest.mark.parametrize("mode", OTHER_MODES)
    def test_matches_oracle(self, mode, case):
        check_msm_case(mode, case)

    def test_env_mode_dispatch(self, monkeypatch):
        monkeypatch.setenv("SPECTRE_MSM_MODE", "glv+signed")
        assert MSM.msm_mode() == "glv+signed"
        monkeypatch.setenv("SPECTRE_MSM_MODE", "bogus")
        with pytest.raises(ValueError):
            MSM.msm_mode()


AGGREGATE_INPUTS = ("random", "all_infinity", "single_bucket")


@functools.lru_cache(maxsize=None)
def _aggregated(c: int, nbuckets: int):
    """(got, want) of `_aggregate_buckets` over one [3, nbuckets] stack, a
    window an input of AGGREGATE_INPUTS: the program of a (c, nbuckets) is
    compiled once for the three. `want` is the plain host sum
    sum_b b * B_b on the host curve."""
    import random
    rng = random.Random(1000 * c + nbuckets)
    base = [p for p in msm_base()[:MSM_N_OTHER_MODES] if p is not None]
    mixed = [rng.choice(base) if rng.random() < 0.7 else None
             for _ in range(nbuckets)]
    mixed[0] = base[0]                   # bucket 0 weighs nothing
    single = [None] * nbuckets
    single[nbuckets - 1] = base[1]       # the highest id: every bit it has
    windows = [mixed, [None] * nbuckets, single]

    def host_sum(buckets):
        acc = None
        for b, pt in enumerate(buckets):
            if pt is not None:
                acc = bn.g1_curve.add(acc, bn.g1_curve.mul(pt, b))
        return None if acc is None else (int(acc[0]), int(acc[1]))

    got = ec.decode_points(
        jax.jit(MSM._aggregate_buckets, static_argnums=1)(
            jnp.stack([ec.encode_points(w) for w in windows]), c))
    return got, [host_sum(w) for w in windows]


class TestAggregateBuckets:
    """`_aggregate_buckets` by split digits against the plain host sum, at
    the two windows the mode cases of this file run (MSM_WINDOWS: 4, even,
    l = h; 5, odd, the hi axis halved once first), for the unsigned bucket
    count 2^c and the signed one 2^(c-1) + 1 (filled to 2^c with the
    identity). Four small programs, each one tree of the grid and one of
    the bit sums."""

    @pytest.mark.parametrize("kind", AGGREGATE_INPUTS)
    @pytest.mark.parametrize("signed", [False, True],
                             ids=["2^c", "2^(c-1)+1"])
    @pytest.mark.parametrize("c", sorted(set(MSM_WINDOWS.values())))
    def test_matches_host_sum(self, c, signed, kind):
        nbuckets = (1 << (c - 1)) + 1 if signed else 1 << c
        got, want = _aggregated(c, nbuckets)
        i = AGGREGATE_INPUTS.index(kind)
        assert got[i] == want[i], (c, nbuckets, kind)
        if kind == "all_infinity":
            assert want[i] is None
        else:
            assert want[i] is not None


@pytest.fixture(scope="module", autouse=True)
def programs_before():
    """Programs each mode's kernel held when this file's first test began
    (another file's, where one process or one worker ran it first)."""
    return kernel_programs()


class TestFixedTableCache:
    def test_hit_and_key_separation(self):
        pts, ss = encode_msm(*msm_case("random", "fixed"))
        MSM.msm(pts, ss, mode="fixed", base_key="t-cache-a")
        builds0, hits0 = MSM._TABLES.builds, MSM._TABLES.hits
        MSM.msm(pts, ss, mode="fixed", base_key="t-cache-a")
        assert MSM._TABLES.hits == hits0 + 1
        assert MSM._TABLES.builds == builds0
        # a different base key must NOT hit the same table
        MSM.msm(pts, ss, mode="fixed", base_key="t-cache-b")
        assert MSM._TABLES.builds == builds0 + 1

    def test_budget_passthrough_uncached(self, monkeypatch):
        tiny = MSM._TableLRU(1024)     # 1 KB: every table passes through
        table = jnp.zeros((4, 8, 3, 16), dtype=jnp.uint32)
        out = tiny.put(("k",), None, table)
        assert out is table
        assert tiny.get(("k",), None) is None   # nothing retained


class TestDefaultWindowTuning:
    def test_pinned_unsigned(self):
        # 2^12 <= n < 2^18 is the chip's choice, twice. PR 32 (every field
        # operation a 16-step scan, a window ~9 ms whatever its buckets):
        # 10 won at 2^14, 2^15 and 2^16. PR 36 (carries resolved in one
        # pass, chip call 2: `msm_windows` at 2^14, c = 8 / 9 / 10, read
        # 0.0916 / 0.0924 / 0.0991 s; at 2^15 0.1665 / 0.1615 / 0.1614; at
        # 2^16 0.3455 / 0.3299 / 0.3141): 8 under 2^15, 10 from there
        assert [MSM.default_window(n) for n in
                (1 << 6, 1 << 7, 1 << 12, 1 << 14, 1 << 15, 1 << 16,
                 1 << 18)] == [4, 7, 8, 8, 10, 10, 13]

    def test_pinned_signed(self):
        # signed digits halve the bucket array -> each size class affords
        # one larger window (the tuning-table change this PR pins)
        assert [MSM.default_window(n, signed=True) for n in
                (1 << 6, 1 << 7, 1 << 12, 1 << 16, 1 << 17, 1 << 18)] == \
            [5, 8, 11, 11, 11, 13]

    def test_fixed_follows_signed(self):
        for n in (1 << 7, 1 << 12, 1 << 17, 1 << 20):
            assert MSM.default_window_fixed(n) == \
                MSM.default_window(n, signed=True)


class TestWindowOverride:
    """SPECTRE_MSM_WINDOW: one env knob retunes every MSM path."""

    def test_override_wins_over_tables(self, monkeypatch):
        monkeypatch.setenv("SPECTRE_MSM_WINDOW", "9")
        assert MSM.window_override() == 9
        for n in (1 << 6, 1 << 12, 1 << 18):
            assert MSM.default_window(n) == 9
            assert MSM.default_window(n, signed=True) == 9
            assert MSM.default_window_fixed(n) == 9

    def test_unset_and_empty_mean_autotune(self, monkeypatch):
        monkeypatch.delenv("SPECTRE_MSM_WINDOW", raising=False)
        assert MSM.window_override() is None
        monkeypatch.setenv("SPECTRE_MSM_WINDOW", "")
        assert MSM.window_override() is None
        # the table's value: the winner of PR 36's sweep on the chip (call 2)
        assert MSM.default_window(1 << 12) == 8

    @pytest.mark.parametrize("bad", ["0", "14", "-3"])
    def test_out_of_range_rejected(self, bad, monkeypatch):
        monkeypatch.setenv("SPECTRE_MSM_WINDOW", bad)
        with pytest.raises(ValueError):
            MSM.window_override()


def _seeded_poly(n: int):
    """n coefficients in the backends' u64-limb form, the same every run."""
    import random
    rng = random.Random(0xD16E57)
    return B.to_arr([rng.randrange(bn.R) for _ in range(n)])


class TestMsmModeCommitments:
    """The ISSUE-2 correctness gate: KZG commitments through the device
    backend are byte-identical across every MSM mode (GLV, signed digits,
    fixed-base tables) AND match the native CPU oracle — the modes change
    work shape, never the committed group element. Commitment-level (not
    full-prove) in the default tier on purpose: this box's XLA CPU client
    segfaults in LLVM under repeated full-prove compile churn; the
    full-prove cross-mode equality is the SPECTRE_BYTEEQ_FULL tier in
    tests/test_plonk.py::TestBackendByteEquality. A polynomial of
    MSM_N_OTHER_MODES coefficients under the tiny SRS: the mode cases'
    programs. The default mode through this backend is
    tests/test_device_prove.py's, call by call (`TestOneChipBatchedCommit`,
    the `msm` cases of its spans)."""

    def test_msm_mode_commitments_byte_identical(self, tiny, monkeypatch):
        srs, coeffs = tiny.srs, _seeded_poly(MSM_N_OTHER_MODES)
        oracle = kzg.commit(srs, coeffs, B.get_backend("cpu"))
        bk = B.get_backend("tpu")
        for mode in OTHER_MODES:
            monkeypatch.setenv("SPECTRE_MSM_MODE", mode)
            got = kzg.commit(srs, coeffs, bk)
            assert got == oracle, \
                f"SPECTRE_MSM_MODE={mode} commitment diverged from oracle"

class TestMsmTableBudgetDegrade:
    """A fixed-base table over the budget degrades the call to glv+signed
    (fault tier, ISSUE 3; beside the kernels it falls back to, so that no
    other file compiles them)."""

    def test_degrades_to_glv_signed_same_point(self, monkeypatch):
        # (the glv+signed program it degrades to is the mode cases')
        pts, sc = msm_case("random", "glv+signed")
        pp, ss = encode_msm(pts, sc)
        want = bn.g1_curve.msm(pts, sc)

        monkeypatch.setattr(MSM._TABLES, "budget", 64)   # nothing fits
        d0 = HEALTH.get("msm_fixed_degraded")
        builds0 = MSM._TABLES.builds
        got = ec.decode_points(
            MSM.msm(pp, ss, mode="fixed", base_key="degrade-test")[None])[0]
        assert got == (int(want[0]), int(want[1]))
        assert HEALTH.get("msm_fixed_degraded") == d0 + 1
        assert MSM._TABLES.builds == builds0     # no table was built

    def test_table_bytes_estimate_exact(self):
        n, c, nbits = 8, 8, 126
        nwin = (nbits + c) // c
        assert MSM._fixed_table_bytes(n, c, nbits) == \
            nwin * 2 * n * 3 * 16 * 4


# `ec.padd` call sites of the served window program (2^14 points, the
# table's c = 8): 14 + 4 + 4 + 4 + 2 (30 while the table said 10: the
# aggregate's two trees are c // 2 and c - c // 2 levels)
WINDOW_PADD_SITES = 28


def _padd_call_sites(monkeypatch, n: int, c: int, counted=False) -> list:
    """The shapes `ec.padd` is traced with, call site by call site, in
    `msm_windows`' program at (n, c): a loop's body is traced once, so this
    is what the program holds to lower, not what it runs. Traced only, on
    shapes: nothing is compiled. `counted`: the served form, whose count of
    windows to run is an argument (ISSUE 38); else the static loop."""
    sites = []
    padd = ec.padd

    def counting(p, q):
        sites.append(p.shape[:-2])
        return padd(p, q)

    monkeypatch.setattr(ec, "padd", counting)
    jax.make_jaxpr(MSM.msm_windows.__wrapped__, static_argnums=2)(
        jax.ShapeDtypeStruct((n, 3, 16), jnp.uint32),
        jax.ShapeDtypeStruct((n, 16), jnp.uint32), c,
        *([jax.ShapeDtypeStruct((), jnp.int32)] if counted else []))
    return sites


# `lax.scan`s in one `ec.padd`'s trace: the CIOS rounds of its two stacked
# `mont_mul`s. It was 39 until PR 36, the other 37 the 16-step carry and
# borrow chains of its 11 stacked `add` / `sub` calls and of the two
# multiplications' tails (592 of an addition's 624 sequential steps).
PADD_SCANS = 2


def _count_scans(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _count_scans(sub)
    return n


class TestWindowProgramSize:
    """What `setup_s` and `prove_s` pay for: every `ec.padd` call site of
    the window program is two loops (the CIOS rounds) and some hundred
    fused programs to lower, compile and load (PERF.md section 5), each
    loop iteration a sequence of device programs of its own when it runs,
    and the served commit's program, `msm_windows` at 2^14 points and the
    table's window, is most of a process's set-up and four fifths of a
    prove. An edit that adds call sites to it (an unrolled chain, a tree
    split in two, a recursion of the aggregate) or a loop to an addition
    (a carry chain written limb by limb) fails here by name."""

    N = 1 << 14

    @pytest.mark.parametrize("counted", [True, False],
                             ids=["served", "static"])
    def test_padd_call_sites_of_the_served_program(self, monkeypatch,
                                                   counted):
        monkeypatch.delenv("SPECTRE_MSM_WINDOW", raising=False)
        c = MSM.default_window(self.N)
        sites = _padd_call_sites(monkeypatch, self.N, c, counted)
        nwin = (254 + c - 1) // c
        # the segmented halving's 14 levels, the emission tree over 15
        # levels' slots (7, 4, 2, 1), then the aggregate, at nwin windows
        # wide: the grid's rows and columns in one tree of c // 2 levels,
        # the c bit sums in one tree of c - c // 2, the two of the chain
        assert sites[:14] == [(self.N >> (lvl + 1),) for lvl in range(14)]
        assert sites[14:18] == [(k, 1 << c) for k in (7, 4, 2, 1)]
        assert {s[0] for s in sites[18:]} == {nwin}
        assert len(sites) == WINDOW_PADD_SITES

    def test_scans_in_one_point_addition(self):
        pt = jax.ShapeDtypeStruct((8, 3, 16), jnp.uint32)
        closed = jax.make_jaxpr(lambda p, q: ec.padd(p, q))(pt, pt)
        assert _count_scans(closed.jaxpr) == PADD_SCANS


class TestWindowsNeeded:
    """`MSM.windows_needed`: the windows of c bits the largest scalar of a
    host column reaches (numpy only)."""

    @pytest.mark.parametrize("top,c,want", [
        (0, 8, 0), (1, 8, 1), (255, 8, 1), (256, 8, 2), ((1 << 32) - 1, 8, 4),
        (1 << 32, 8, 5), ((1 << 64) - 1, 8, 8), (1 << 64, 8, 9),
        ((1 << 128) - 1, 10, 13), (bn.R - 1, 8, 32), (bn.R - 1, 10, 26),
        (bn.R - 1, 4, 64), (15, 4, 1), (16, 4, 2)])
    def test_counts(self, top, c, want):
        from spectre_tpu.native import host
        col = host.ints_to_limbs([top >> 3, top, 0, top >> 1])
        got = MSM.windows_needed(col, c)
        assert got == want and got.dtype == np.int32
        assert want == MSM.window_count(top.bit_length(), c)


class TestKernelShapesPinned:
    """The sharing itself: this file leaves each mode's kernel compiled for
    ONE (n, c), the shared one. A case that brings a shape of its own adds
    tens of seconds to a run with a cold compile cache; it shows up here as
    a failure and not there as a slower suite. The last class of the file:
    it runs after every class that calls a kernel."""

    @pytest.mark.parametrize("mode", OTHER_MODES)
    def test_one_program_a_mode(self, programs_before, mode):
        # the shared shape once more: a hit after the mode cases, the one
        # compile where this class runs alone
        MSM.msm(*encode_msm(*msm_case("skewed", mode)), mode=mode)
        assert kernel_programs()[mode] - programs_before[mode] <= 1, \
            f"{mode}: a test of this file compiled another (n, c)"

    def test_the_default_windows_are_the_shared_ones(self):
        n = MSM_N_OTHER_MODES
        assert MSM_WINDOWS == {
            "vanilla": MSM.default_window(MSM_N),
            "glv": MSM.default_window(2 * n),
            "glv+signed": MSM.default_window(2 * n, signed=True),
            "fixed": MSM.default_window_fixed(2 * n)}
