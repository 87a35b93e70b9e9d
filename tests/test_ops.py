"""Device kernels (ops/) vs host oracles, on the CPU backend."""

import hashlib
import secrets

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.ops import ec, field_ops as F, limbs as L
from spectre_tpu.ops import ntt as NTT, poseidon as POS, sha256 as SHA

from _shapes import MSM_N_OTHER_MODES, check_msm_case


def rand_fr(n):
    return [secrets.randbelow(bn.R) for _ in range(n)]


def _field(field):
    return (F.fr_ctx(), bn.R) if field == "fr" else (F.fq_ctx(), bn.P)


class TestLimbs:
    def test_roundtrip(self):
        vals = [0, 1, bn.R - 1, 2**255 - 1, 12345]
        assert L.limbs16_to_ints(L.ints_to_limbs16(vals)) == vals

    def test_u64_u16_conversion(self):
        vals = rand_fr(8)
        from spectre_tpu.native.host import ints_to_limbs
        u64 = ints_to_limbs(vals)
        u16 = L.u64limbs_to_u16limbs(u64)
        assert L.limbs16_to_ints(u16) == vals
        assert np.array_equal(L.u16limbs_to_u64limbs(u16), u64)

    # ISSUE 39: a column crosses as its rows of 32 bytes and the device
    # splits the limbs and pads the rows; the host split of the host-padded
    # stack is the oracle. ONE input shape ([2, 8, 8]) for every case
    WIRE_ROWS = {
        "random": lambda: rand_fr(16),
        "zero": lambda: [0] * 16,
        "r_minus_1": lambda: [bn.R - 1] * 16,
        "all_ones_words": lambda: [2**256 - 1] * 16,
        # a column shorter than n: zero-filled up to n by `stack_rows`
        "short_column": lambda: rand_fr(8) + rand_fr(5),
    }

    @pytest.mark.parametrize("n_out", [None, 32], ids=["unpadded", "padded"])
    @pytest.mark.parametrize("rows", sorted(WIRE_ROWS))
    def test_device_split_equals_the_host_split(self, rows, n_out):
        from spectre_tpu.native.host import ints_to_limbs
        from spectre_tpu.plonk.backend import stack_rows
        vals = self.WIRE_ROWS[rows]()
        cols = [ints_to_limbs(vals[:8]), ints_to_limbs(vals[8:])]
        stack = stack_rows(cols, 8)
        assert stack.shape == (2, 8, 4)
        assert not stack[1, len(vals) - 8:].any()
        packed = L.pack_u64limbs(stack)
        assert packed.shape == (2, 8, 8) and packed.dtype == np.uint32
        assert np.shares_memory(packed, stack)
        padded = np.zeros((2, n_out or 8, 4), dtype=np.uint64)
        padded[:, :8] = stack
        want = L.u64limbs_to_u16limbs(padded.reshape(-1, 4)).reshape(
            2, n_out or 8, 16)
        got = L.split_limbs16(jnp.asarray(packed), n_out)
        assert got.dtype == jnp.uint32
        assert np.array_equal(np.asarray(got), want)


class TestFieldOps:
    def test_mul_add_sub_neg(self):
        ctx = F.fr_ctx()
        a, b = rand_fr(64), rand_fr(64)
        am, bm = jnp.asarray(ctx.encode(a)), jnp.asarray(ctx.encode(b))
        assert ctx.decode(F.mont_mul(ctx, am, bm)) == [x * y % bn.R for x, y in zip(a, b)]
        assert ctx.decode(F.add(ctx, am, bm)) == [(x + y) % bn.R for x, y in zip(a, b)]
        assert ctx.decode(F.sub(ctx, am, bm)) == [(x - y) % bn.R for x, y in zip(a, b)]
        assert ctx.decode(F.neg(ctx, am)) == [(-x) % bn.R for x in a]

    def test_edge_values(self):
        ctx = F.fr_ctx()
        e = [0, 1, bn.R - 1, bn.R - 2]
        em = jnp.asarray(ctx.encode(e))
        assert ctx.decode(F.mont_mul(ctx, em, em)) == [x * x % bn.R for x in e]
        assert ctx.decode(F.neg(ctx, jnp.asarray(ctx.encode([0])))) == [0]

    def test_inv_and_pow(self):
        ctx = F.fr_ctx()
        a = rand_fr(8)
        am = jnp.asarray(ctx.encode(a))
        assert ctx.decode(jax.jit(lambda x: F.inv(ctx, x))(am)) == \
            [pow(x, -1, bn.R) for x in a]
        assert ctx.decode(F.mont_pow(ctx, am, 97)) == [pow(x, 97, bn.R) for x in a]

    def test_fq_ctx(self):
        ctx = F.fq_ctx()
        a, b = [secrets.randbelow(bn.P) for _ in range(8)], [secrets.randbelow(bn.P) for _ in range(8)]
        am, bm = jnp.asarray(ctx.encode(a)), jnp.asarray(ctx.encode(b))
        assert ctx.decode(F.mont_mul(ctx, am, bm)) == [x * y % bn.P for x, y in zip(a, b)]

    @pytest.mark.parametrize("field", ["fr", "fq"])
    def test_sub_zero_is_bit_exact(self, field):
        """a - 0 is a's own limbs, 0 - 0 the zero limbs: `sub` adds p - b,
        and p - 0 = p has to fold back to the canonical representative
        (equal values mod p is what `test_mul_add_sub_neg` decodes; equal
        limbs was checked only on the Pallas mirror, which is gone)."""
        ctx, p = _field(field)
        a = [0, 1, p - 1] + [secrets.randbelow(p) for _ in range(5)]
        am = jnp.asarray(ctx.encode(a))
        got = jax.jit(lambda x, y: F.sub(ctx, x, y))(am, jnp.zeros_like(am))
        assert np.array_equal(np.asarray(got), np.asarray(am))


# The 16-step scan forms `field_ops` held until PR 36 (one limb a loop
# iteration), kept here as the oracle of the one-pass forms that took their
# place: equal limbs AND equal carry / borrow out, not equal values mod p.

def _carry_propagate_scan(t):
    tT = jnp.moveaxis(t, -1, 0)

    def step(carry, ti):
        cur = ti + carry
        return cur >> 16, cur & F.MASK

    carry, outs = jax.lax.scan(step, jnp.zeros_like(tT[0]), tT)
    return jnp.moveaxis(outs, 0, -1), carry


def _sub_limbs_scan(a, b):
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    aT = jnp.moveaxis(jnp.broadcast_to(a, shape), -1, 0)
    bT = jnp.moveaxis(jnp.broadcast_to(b, shape), -1, 0)

    def step(borrow, ab):
        ai, bi = ab
        cur = ai - bi - borrow  # uint32 wraps
        return (cur >> 16) & np.uint32(1), cur & F.MASK  # wrap iff borrow

    borrow, outs = jax.lax.scan(step, jnp.zeros_like(aT[0]), (aT, bT))
    return jnp.moveaxis(outs, 0, -1), borrow


def _cond_sub_p_scan(ctx, a):
    diff, borrow = _sub_limbs_scan(a, jnp.broadcast_to(ctx.p_limbs, a.shape))
    return jnp.where((borrow == 0)[..., None], diff, a)


def _add_scan(ctx, a, b):
    return _cond_sub_p_scan(ctx, _carry_propagate_scan(a + b)[0])


def _sub_scan(ctx, a, b):
    pb, _ = _sub_limbs_scan(jnp.broadcast_to(ctx.p_limbs, b.shape), b)
    return _add_scan(ctx, a, pb)


def _neg_scan(ctx, a):
    pb, _ = _sub_limbs_scan(jnp.broadcast_to(ctx.p_limbs, a.shape), a)
    is_zero = jnp.all(a == 0, axis=-1, keepdims=True)
    return jnp.where(is_zero, jnp.zeros_like(a), _cond_sub_p_scan(ctx, pb))


def _limbs(vals):
    return jnp.asarray(L.ints_to_limbs16(list(vals)))


def _rand_ints(p, n, seed):
    rng = np.random.default_rng(seed)
    return [int.from_bytes(rng.bytes(40), "little") % p for _ in range(n)]


def _rand_below(p, shape, seed):
    return _limbs(_rand_ints(p, int(np.prod(shape)), seed)).reshape(
        tuple(shape) + (16,))


def _same(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape and g.dtype == w.dtype
        assert np.array_equal(np.asarray(g), np.asarray(w))


# accumulators handed to `_carry_propagate`: name -> f(p) -> [..., 16] uint32
ACCUMULATORS = {
    # 0xFFFF... + 1: the carry made in limb 0 passes through all sixteen
    "ripple_through_all_limbs": lambda p: _limbs([(1 << 256) - 1]).at[0, 0].add(1),
    "p_minus_1_twice": lambda p: _limbs([p - 1]) * 2,
    "zero_and_one": lambda p: _limbs([0, 1]),
    # what the CIOS rounds hand over: limbs up to 2^24 - 1, every one of them
    "limbs_at_2_24_minus_1": lambda p: jnp.full((3, 16), (1 << 24) - 1, jnp.uint32),
    "limbs_below_2_24": lambda p: jnp.asarray(np.random.default_rng(24).integers(
        0, 1 << 24, size=(64, 16), dtype=np.uint32)),
    # a carry of more than one out of the top limb comes back as before
    "top_carry_above_one": lambda p: jnp.zeros((2, 16), jnp.uint32).at[:, 15].set(
        jnp.asarray([0x30000, 0x2FFFF], jnp.uint32)).at[1, 14].set(0x10000),
    "limbs_at_2_31_minus_1": lambda p: jnp.full((2, 16), (1 << 31) - 1, jnp.uint32),
    "stacked_6_n": lambda p: _rand_below(p, (6, 5), 1) + _rand_below(p, (6, 5), 2),
}

# operand pairs of `_sub_limbs`, `add`, `sub`: name -> f(p) -> (a, b) limbs
PAIRS = {
    "a_minus_a": lambda p: (_rand_below(p, (8,), 3),) * 2,
    "zero_minus_one": lambda p: (_limbs([0]), _limbs([1])),
    # the borrow made in limb 0 passes through a run of fourteen zero limbs
    "borrow_through_zero_limbs": lambda p: (_limbs([1 << 240, 1 << 240]),
                                           _limbs([1, 0xFFFF])),
    "sum_is_p_exactly": lambda p: (_limbs([1, p - 1, p // 2]),
                                   _limbs([p - 1, 1, p - p // 2])),
    "p_minus_1_twice": lambda p: (_limbs([p - 1]), _limbs([p - 1])),
    "minus_zero": lambda p: (_limbs([0, 1, p - 1]), _limbs([0, 0, 0])),
    "edges_crossed": lambda p: tuple(
        _limbs(v) for v in zip(*[(a, b) for a in _edges(p) for b in _edges(p)])),
    "stacked_6_n_against_16": lambda p: (_rand_below(p, (6, 7), 4),
                                         _rand_below(p, (), 5)),
    "random_rows": lambda p: (_rand_below(p, (64,), 6), _rand_below(p, (64,), 7)),
}


# normalized values under 2p, what `_cond_sub_p` takes
BELOW_2P = {
    "below_p": lambda p: _limbs([0, 1, p - 1, p - (1 << 16)]),
    "at_and_above_p": lambda p: _limbs([p, p + 1, 2 * p - 1, p + (1 << 240)]),
    "stacked_6_n": lambda p: _limbs(
        [x + y for x, y in zip(_rand_ints(p, 54, 8), _rand_ints(p, 54, 9))]
    ).reshape(6, 9, 16),
}


def _edges(p):
    return [0, 1, p - 1, p - 2, (1 << 240) - 1, 0xFFFF, p - (1 << 16),
            (1 << 253) - 1]


class TestCarryChains:
    """`_carry_propagate`, `_sub_limbs`, `_cond_sub_p` and the three
    operations built on them against their scan forms, limb for limb."""

    @pytest.mark.parametrize("case", sorted(ACCUMULATORS))
    @pytest.mark.parametrize("field", ["fr", "fq"])
    def test_carry_propagate(self, field, case):
        _, p = _field(field)
        t = ACCUMULATORS[case](p)
        _same(jax.jit(F._carry_propagate)(t), _carry_propagate_scan(t))

    @pytest.mark.parametrize("case", sorted(PAIRS))
    @pytest.mark.parametrize("field", ["fr", "fq"])
    def test_sub_limbs(self, field, case):
        _, p = _field(field)
        a, b = PAIRS[case](p)
        _same(jax.jit(F._sub_limbs)(a, b), _sub_limbs_scan(a, b))
        _same(jax.jit(F._sub_limbs)(b, a), _sub_limbs_scan(b, a))

    @pytest.mark.parametrize("case", sorted(BELOW_2P))
    @pytest.mark.parametrize("field", ["fr", "fq"])
    def test_cond_sub_p(self, field, case):
        ctx, p = _field(field)
        a = BELOW_2P[case](p)
        _same(jax.jit(lambda x: F._cond_sub_p(ctx, x))(a),
              _cond_sub_p_scan(ctx, a))

    @pytest.mark.parametrize("case", sorted(PAIRS))
    @pytest.mark.parametrize("field", ["fr", "fq"])
    def test_add_sub_neg(self, field, case):
        ctx, p = _field(field)
        a, b = PAIRS[case](p)
        total = jax.jit(lambda x, y: F.add(ctx, x, y))(a, b)
        diff = jax.jit(lambda x, y: F.sub(ctx, x, y))(a, b)
        minus = jax.jit(lambda x: F.neg(ctx, x))(b)
        _same((total, diff, minus), (_add_scan(ctx, a, b),
                                     _sub_scan(ctx, a, b), _neg_scan(ctx, b)))
        ints = lambda x: L.limbs16_to_ints(np.asarray(x).reshape(-1, 16))  # noqa: E731
        ai, bi = ints(jnp.broadcast_to(a, total.shape)), \
            ints(jnp.broadcast_to(b, total.shape))
        assert ints(total) == [(x + y) % p for x, y in zip(ai, bi)]
        assert ints(diff) == [(x - y) % p for x, y in zip(ai, bi)]
        assert ints(minus) == [(-y) % p for y in ints(b)]

    @pytest.mark.parametrize("field", ["fr", "fq"])
    def test_mont_mul_tail(self, field):
        """The CIOS rounds' accumulator through the new carry pass and the
        conditional subtraction: products of edge values and random rows
        against Python ints."""
        ctx, p = _field(field)
        vals = _edges(p) + _rand_ints(p, 24, 10)
        a = jnp.asarray(ctx.encode([x for x in vals for _ in vals]))
        b = jnp.asarray(ctx.encode([y for _ in vals for y in vals]))
        got = np.asarray(jax.jit(lambda x, y: F.mont_mul(ctx, x, y))(a, b))
        assert ctx.decode(got) == [x * y % p for x in vals for y in vals]
        assert (got < (1 << 16)).all()


class TestNTT:
    def test_vs_native_and_roundtrip(self):
        k = 6
        w = bn.fr_root_of_unity(k)
        data = rand_fr(1 << k)
        ctx = F.fr_ctx()
        dm = jnp.asarray(ctx.encode(data))
        got = ctx.decode(jax.jit(lambda a: NTT.ntt(a, w))(dm))
        from spectre_tpu.native import host
        dl = host.ints_to_limbs(data)
        host.fr_ntt(dl, w)
        assert got == host.limbs_to_ints(dl)
        back = ctx.decode(jax.jit(lambda a: NTT.intt(a, w))(jnp.asarray(ctx.encode(got))))
        assert back == data

    def test_coset_roundtrip(self):
        k = 5
        w = bn.fr_root_of_unity(k)
        data = rand_fr(1 << k)
        ctx = F.fr_ctx()
        dm = jnp.asarray(ctx.encode(data))
        got = ctx.decode(jax.jit(
            lambda a: NTT.coset_intt(NTT.coset_ntt(a, w, 5), w, 5))(dm))
        assert got == data

    def test_coset_evaluates_on_coset(self):
        # coset_ntt(a, w, g)[i] should equal poly(g * w^i)
        k = 3
        w = bn.fr_root_of_unity(k)
        g = 7
        coeffs = rand_fr(1 << k)
        ctx = F.fr_ctx()
        got = ctx.decode(NTT.coset_ntt(jnp.asarray(ctx.encode(coeffs)), w, g))
        for i in range(1 << k):
            x = g * pow(w, i, bn.R) % bn.R
            want = sum(c * pow(x, j, bn.R) for j, c in enumerate(coeffs)) % bn.R
            assert got[i] == want


class TestEC:
    def test_complete_add_cases(self):
        g = bn.G1_GEN
        pts_a = [g, bn.g1_curve.mul(g, 5), g, g, None, None]
        pts_b = [g, bn.g1_curve.mul(g, 9), None, bn.g1_curve.neg(g), g, None]
        got = ec.decode_points(jax.jit(ec.padd)(
            ec.encode_points(pts_a), ec.encode_points(pts_b)))
        want = [bn.g1_curve.add(a, b) for a, b in zip(pts_a, pts_b)]
        assert got == [None if w is None else (int(w[0]), int(w[1])) for w in want]

    @pytest.mark.parametrize("case", ["mixed_mask", "infinity"])
    def test_cneg(self, case):
        """`ec.cneg` against the host curve's negation, at the batch of
        `test_complete_add_cases` (one `padd` program). `mixed_mask`: the
        masked rows are negated and the others untouched, bit for bit.
        `infinity`: the caveat of `cneg`'s docstring, -(0:1:0) = (0:p-1:0)
        is another representative of the identity: it decodes to the
        identity, and P + cneg(inf) == P. (At the parent only the Pallas
        mirror `_k_cneg` was compared with anything.)"""
        g = bn.G1_GEN
        pts = [g, bn.g1_curve.mul(g, 5), None, bn.g1_curve.mul(g, 9), g, None]
        enc = ec.encode_points(pts)
        cneg = jax.jit(ec.cneg)
        if case == "mixed_mask":
            mask = [True, False, True, True, False, False]
            got = cneg(jnp.asarray(mask), enc)
            want = [bn.g1_curve.neg(p) if m and p is not None else p
                    for m, p in zip(mask, pts)]
            assert ec.decode_points(got) == [
                None if w is None else (int(w[0]), int(w[1])) for w in want]
            keep = ~np.asarray(mask)
            assert np.array_equal(np.asarray(got)[keep],
                                  np.asarray(enc)[keep])
        else:
            inf = ec.inf_point((len(pts),))
            neg_inf = cneg(jnp.ones(len(pts), bool), inf)
            assert ec.decode_points(neg_inf) == [None] * len(pts)
            assert ec.decode_points(jax.jit(ec.padd)(enc, neg_inf)) == \
                ec.decode_points(enc)


class TestMSMWindowOverride:
    def test_override_result_unchanged(self, monkeypatch):
        """An overridden window changes the work shape, never the point:
        the default mode under SPECTRE_MSM_WINDOW=3 against the host
        curve's MSM. A program of its own whatever the shape (no other
        test runs `msm_windows` at c = 3), so at the small one: some 15 s
        with a cold compile cache. The default window's cases are
        tests/test_device_prove.py::TestDefaultMsm."""
        monkeypatch.setenv("SPECTRE_MSM_WINDOW", "3")
        check_msm_case("vanilla", "random", n=MSM_N_OTHER_MODES)


class TestSHA256:
    def test_vs_hashlib(self):
        msgs = [secrets.token_bytes(100) for _ in range(8)]
        assert SHA.sha256_many(msgs) == [hashlib.sha256(m).digest() for m in msgs]

    def test_padding_boundaries(self):
        for ln in (0, 55, 56, 63, 64, 65):
            m = b"a" * ln
            assert SHA.sha256_many([m])[0] == hashlib.sha256(m).digest()

    def test_hash_pairs(self):
        l = [secrets.token_bytes(32) for _ in range(4)]
        r = [secrets.token_bytes(32) for _ in range(4)]
        lw = jnp.asarray(np.stack([SHA.bytes32_to_words(x) for x in l]))
        rw = jnp.asarray(np.stack([SHA.bytes32_to_words(x) for x in r]))
        got = [SHA.words_to_bytes32(x) for x in np.asarray(SHA.hash_pairs(lw, rw))]
        assert got == [hashlib.sha256(a + b).digest() for a, b in zip(l, r)]


class TestPoseidon:
    def test_native_equals_device(self):
        state = rand_fr(POS.T)
        want = POS.permute_native(state)
        ctx = F.fr_ctx()
        sm = jnp.asarray(ctx.encode(state)).reshape(1, POS.T, 16)
        assert ctx.decode(jax.jit(POS.permute)(sm)) == want

    def test_sponge(self):
        s1 = POS.PoseidonSponge()
        s1.absorb([1, 2, 3])
        h1 = s1.squeeze()
        s2 = POS.PoseidonSponge()
        s2.absorb([1, 2, 3])
        assert s2.squeeze() == h1
        s3 = POS.PoseidonSponge()
        s3.absorb([1, 2, 4])
        assert s3.squeeze() != h1
        assert 0 < h1 < bn.R

    def test_constants_shape(self):
        rc, mds = POS.constants()
        assert len(rc) == (POS.R_F + POS.R_P) * POS.T
        assert len(mds) == POS.T and all(len(row) == POS.T for row in mds)
        # MDS must be invertible (Cauchy construction): det != 0 via rank over Fr
        # cheap sanity: no duplicate rows
        assert len({tuple(r) for r in mds}) == POS.T

    def test_golden_vectors_pinned(self):
        """Pinned outputs of the halo2-base-procedure Grain derivation
        (T=12, RATE=11, R_F=8, R_P=65, SECURE_MDS=0). These are derived
        in-repo (no external oracle available offline — see module note);
        pinning makes ANY drift in the generation procedure loud, and gives
        the cross-check target for when a pse-poseidon oracle is available."""
        rc, mds = POS.constants()
        assert rc[0] == 0x2F8B21C35B9D040439B4A4C99454409736FE5CE816A8150E6E27E30E2C886A9B
        assert rc[-1] == 0x24E539B23BAD276B2DAFB1E5C8F68C7B1E03AE757923A01D3C62233927647CA4
        assert mds[0][0] == 0x1B3C91FF6B67F23544228B250E678D20A3122EF1607685B28AF981E84F6DE352
        sp = POS.PoseidonSponge()
        sp.absorb([1, 2, 3])
        assert sp.squeeze() == 0x1B7F414A1AC0F4662FA50E8BA7BD7ED853D2591C20DF0ED3F4610CCDC9048C9E
        assert POS.permute_native([0] * 12)[0] == \
            0x24DA301E2F781BD5A7CD94470F24A69843EEEF45AE7FAE411482F431567A2A44


class TestMxuField:
    """MXU int8-limb matmul Montgomery multiply (ops/field_mxu.py): exact
    equality with the CIOS path on random + edge values, both BN254 fields.
    (CPU-JAX executes the same graph the TPU tiles onto the MXU; a
    throughput claim needs a chip run — see PERF.md.)"""

    def test_matches_cios_fr_fq(self):
        import numpy as np
        from spectre_tpu.ops import field_mxu as M
        rng = np.random.default_rng(7)
        for ctx in (F.fr_ctx(), F.fq_ctx()):
            xs = [int.from_bytes(rng.bytes(32), "little") % ctx.p
                  for _ in range(32)]
            ys = [int.from_bytes(rng.bytes(32), "little") % ctx.p
                  for _ in range(32)]
            xs += [0, 1, ctx.p - 1, ctx.p // 2, 2]
            ys += [ctx.p - 1, 0, ctx.p - 1, 2, ctx.p // 3]
            a, b = ctx.encode_np(xs), ctx.encode_np(ys)
            ref = np.asarray(F._mont_mul_cios(ctx, a, b))
            got = np.asarray(M.mont_mul(ctx, a, b))
            assert np.array_equal(ref, got), ctx.name
            for x, y, z in zip(xs, ys, ctx.decode(got)):
                assert z == x * y % ctx.p

    def test_enable_mxu_dispatch_flag(self):
        # mont_mul dispatches on the module flag at trace time (no global
        # rebinding), so stale `from field_ops import mont_mul` bindings
        # still follow enable_mxu() swaps.
        from spectre_tpu.ops import field_mxu as M
        before = F._USE_MXU
        ctx = F.fr_ctx()
        a, b = ctx.encode([3, 5]), ctx.encode([7, 11])
        routed = []
        real = M.mont_mul

        def spy(c, x, y):
            routed.append(True)
            return real(c, x, y)

        M.mont_mul = spy
        try:
            F.enable_mxu(True)
            got = ctx.decode(F.mont_mul(ctx, a, b))
            assert routed, "enable_mxu(True) did not route through field_mxu"
            assert got == [21, 55]
            F.enable_mxu(False)
            routed.clear()
            got = ctx.decode(F.mont_mul(ctx, a, b))
            assert not routed, "enable_mxu(False) still routes through field_mxu"
            assert got == [21, 55]
        finally:
            M.mont_mul = real
            # restore whatever the process was configured with (e.g. a
            # suite-wide SPECTRE_FIELD_IMPL=mxu run must stay on mxu)
            F.enable_mxu(before)


class TestGrainSecondSource:
    """Independent re-derivation of the Grain LFSR stream (integer-register
    implementation, written from the Poseidon reference generator's spec:
    b_{i+80} = b_{i+62}^b_{i+51}^b_{i+38}^b_{i+23}^b_{i+13}^b_i, 160 warmup
    outputs discarded, von Neumann pair filtering) cross-checked against
    ops.poseidon.GrainLFSR. Catches tap/order/init transcription bugs; true
    pse-poseidon BYTE parity still needs an external oracle (none exists
    offline — ops/poseidon.py header records the caveat)."""

    @staticmethod
    def _grain_int(field_bits, t, r_f, r_p, n_bits_out):
        # init word: 2b field_type=1 | 4b sbox=0 | 12b field_bits | 12b t |
        # 10b r_f | 10b r_p | 30x1  (MSB-first), register bit 79 = b_0
        init = (1 << 78) | (0 << 74) | (field_bits << 62) | (t << 50) \
            | (r_f << 40) | (r_p << 30) | ((1 << 30) - 1)
        state = init  # bit 79-i of `state` is stream bit i
        out = []

        def step():
            nonlocal state
            # taps relative to the oldest bit b_i: 62,51,38,23,13,0
            b = 0
            for tap in (62, 51, 38, 23, 13, 0):
                b ^= (state >> (79 - tap)) & 1
            state = ((state << 1) & ((1 << 80) - 1)) | b
            return b

        for _ in range(160):
            step()
        while len(out) < n_bits_out:
            if step():
                out.append(step())
            else:
                step()
        return out

    def test_streams_match(self):
        from spectre_tpu.ops.poseidon import GrainLFSR
        for (fb, t, rf, rp) in [(254, 12, 8, 65), (254, 3, 8, 57)]:
            g = GrainLFSR(fb, t, rf, rp)
            mine = self._grain_int(fb, t, rf, rp, 600)
            theirs = [g.next_filtered_bit() for _ in range(600)]
            assert mine == theirs, (fb, t, rf, rp)

    def test_first_round_constant_sanity(self):
        # rejection-sampled first constant is a valid Fr element and stable
        # (golden of THIS derivation; flags accidental drift)
        from spectre_tpu.fields import bn254
        from spectre_tpu.ops.poseidon import GrainLFSR
        g = GrainLFSR(254, 12, 8, 65)
        c0 = g.next_field_element(bn254.R, 254)
        assert 0 < c0 < bn254.R
        g2 = GrainLFSR(254, 12, 8, 65)
        assert g2.next_field_element(bn254.R, 254) == c0


class TestField384:
    """BLS12-381 device field (24-limb) + batched G1 decompression."""

    def test_mont_mul_matches_host(self):
        import numpy as np
        from spectre_tpu.fields import bls12_381 as bls
        from spectre_tpu.ops import field384 as F3
        ctx = F3.bls_fq_ctx()
        rng = np.random.default_rng(11)
        xs = [int.from_bytes(rng.bytes(48), "little") % ctx.p for _ in range(16)]
        ys = [int.from_bytes(rng.bytes(48), "little") % ctx.p for _ in range(16)]
        xs += [0, 1, ctx.p - 1]
        ys += [ctx.p - 1, 0, ctx.p - 1]
        a, b = ctx.encode_np(xs), ctx.encode_np(ys)
        got = ctx.decode(np.asarray(F3.mont_mul(ctx, a, b)))
        for x, y, z in zip(xs, ys, got):
            assert z == x * y % ctx.p

    def test_decompress_batch_matches_host(self):
        from spectre_tpu.fields import bls12_381 as bls
        from spectre_tpu.ops.field384 import g1_decompress_batch
        # mix of sign bits (negate half the points)
        pts = []
        for i in range(6):
            p = bls.sk_to_pk(7919 * i + 3)
            if i % 2:
                p = bls.g1_curve.neg(p)
            pts.append(bls.g1_compress(p))
        got = g1_decompress_batch(pts)
        for k, g in zip(pts, got):
            x, y = bls.g1_decompress(k)
            assert (int(x), int(y)) == g

    def test_decompress_rejects_off_curve(self):
        from spectre_tpu.fields import bls12_381 as bls
        from spectre_tpu.ops.field384 import g1_decompress_batch
        good = bls.g1_compress(bls.sk_to_pk(5))
        # find an x with no sqrt(x^3+4): x=1 -> 5 is a QR? craft by search
        for cand in range(1, 50):
            if pow((cand ** 3 + 4) % bls.P, (bls.P - 1) // 2, bls.P) != 1:
                bad_x = cand
                break
        bad = bytearray(int(bad_x).to_bytes(48, "big"))
        bad[0] |= 0x80
        with pytest.raises(AssertionError):
            g1_decompress_batch([good, bytes(bad)])
