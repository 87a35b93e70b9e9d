"""Vectorized Montgomery arithmetic on 16-bit limb tensors (JAX).

The device-side equivalent of the reference's `halo2curves` field arithmetic
(SURVEY.md §2b N1), designed for the TPU VPU: all values are [..., 16] uint32
tensors of 16-bit limbs; multiplication is 16 CIOS rounds (one `lax.scan`,
the only loop of this module's arithmetic), each a fully vectorized
multiply-accumulate over the batch; carries and borrows are resolved in one
pass over the limb axis (`_resolve`), not limb by limb; no 64-bit integers
anywhere. Montgomery radix R = 2^256 (matches the native C++ lib, so host <->
device form conversion is pure layout change).

Magnitude analysis (why uint32 never overflows): each CIOS round adds at most
~2^18 per accumulator column; over 16 rounds plus shifted carries the
accumulators stay < 2^24.

Works identically under `jit` on TPU and CPU backends; tests compare against
the C++/Python oracle on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..fields import bn254
from . import limbs as L

NLIMBS = 16
MASK = np.uint32(0xFFFF)


class FieldCtx:
    """Per-modulus constant set, device-resident after first use."""

    def __init__(self, p: int, name: str):
        self.p = p
        self.name = name
        # constants kept as NUMPY so they lift to fresh embedded constants in
        # every trace (a cached jnp array created inside a jit trace is a
        # leaked tracer — learned the hard way)
        self.p_limbs = L.int_to_limbs16(p)
        self.n0inv16 = np.uint32((-pow(p, -1, 1 << 16)) % (1 << 16))
        r = (1 << 256) % p
        self.r_mod_p = r
        self.r2 = L.int_to_limbs16((r * r) % p)
        self.one_mont = L.int_to_limbs16(r)
        self.zero = np.zeros(NLIMBS, dtype=np.uint32)

    # -- host-side encode/decode (pure numpy/ints: safe to call anywhere,
    #    including from inside cached constant builders used under jit) --
    def encode_np(self, vals) -> np.ndarray:
        """Python ints -> Montgomery limb array [n, 16] (host computation)."""
        r = self.r_mod_p
        return L.ints_to_limbs16([(int(v) % self.p) * r % self.p for v in vals])

    def encode(self, vals) -> np.ndarray:
        """Alias of encode_np — numpy out, so results are safe to cache."""
        return self.encode_np(vals)

    def decode(self, arr) -> list[int]:
        """Montgomery limb tensor/array -> Python ints (host computation)."""
        rinv = pow(self.r_mod_p, -1, self.p)
        return [v * rinv % self.p for v in L.limbs16_to_ints(np.asarray(arr))]


@functools.cache
def fr_ctx() -> FieldCtx:
    return FieldCtx(bn254.R, "bn254_fr")


@functools.cache
def fq_ctx() -> FieldCtx:
    return FieldCtx(bn254.P, "bn254_fq")


# ---------------------------------------------------------------------------
# core arithmetic (all shapes [..., 16] uint32)
# ---------------------------------------------------------------------------

def _resolve(generate, propagate):
    """Carry (or borrow) INTO each limb, and out of the top one, of a chain
    whose limb i makes a carry whatever comes in (`generate[..., i]`) or
    passes on the one that comes in (`propagate[..., i]`; never both): the
    recurrence out_i = generate_i | (propagate_i & out_{i-1}), which is a
    binary addition's own. So the bits of a value are gathered into one
    word each, G and P, and ONE integer addition ripples every carry
    through its run of propagating limbs: with X = G | P and Y = G, X & Y
    generates where G does and X ^ Y propagates where P does, and the sum's
    bit i is P_i ^ in_i. No loop over the limbs: a reduction over the limb
    axis, three word operations, a broadcast back. [..., k] bools, k <= 31
    -> ([..., k] uint32 of 0/1, [...] uint32 of 0/1)."""
    k = generate.shape[-1]
    shifts = np.arange(k, dtype=np.uint32)
    weights = np.uint32(1) << shifts
    zero = np.uint32(0)
    g = jnp.sum(jnp.where(generate, weights, zero), axis=-1, dtype=jnp.uint32)
    p = jnp.sum(jnp.where(propagate, weights, zero), axis=-1,
                dtype=jnp.uint32)
    into = ((g | p) + g) ^ p        # bit i: into limb i; bit k: out of the top
    return (into[..., None] >> shifts) & np.uint32(1), into >> np.uint32(k)


def _carry_bits(s):
    """`_carry_propagate` for limbs <= 0x1FFFE (a sum of two normalized
    limbs): each limb makes at most one carry, and one that overflowed is
    at most 0xFFFE below, so it cannot pass one on as well."""
    low = s & MASK
    into, out = _resolve(s > MASK, low == MASK)
    return (low + into) & MASK, out


def _carry_propagate(t):
    """Full carry propagation of a [..., k] uint32 accumulator tensor, little-
    endian 16-bit limbs (any uint32: the CIOS rounds hand over limbs up to
    ~2^24). Returns the same-shape tensor with entries < 2^16 and the carry
    out of the top limb. One pass, no loop (PERF.md section 5, PR 36: a
    16-step `lax.scan` here was 16 x ~7 device programs): the high halves
    move up one limb first, after which every limb is <= 0x1FFFE and
    `_carry_bits` resolves what is left."""
    high = t >> 16
    moved = jnp.concatenate(
        [jnp.zeros_like(high[..., :1]), high[..., :-1]], axis=-1)
    out, carry = _carry_bits((t & MASK) + moved)
    return out, high[..., -1] + carry


def _sub_limbs(a, b):
    """a - b over normalized limbs; returns (diff limbs, final borrow 0/1).
    A limb borrows whatever comes in where a < b and passes a borrow on
    where a == b (`_resolve`); the limb itself is a - b - borrow in uint32,
    which wraps where it borrows, and the mask keeps the low 16 bits."""
    a, b = jnp.asarray(a), jnp.asarray(b)
    into, out = _resolve(a < b, a == b)
    return (a - b - into) & MASK, out


def _cond_sub_p(ctx: FieldCtx, a):
    """a if a < p else a - p (a < 2p, limbs normalized)."""
    diff, borrow = _sub_limbs(a, ctx.p_limbs)
    return jnp.where((borrow == 0)[..., None], diff, a)


def add(ctx: FieldCtx, a, b):
    t, _ = _carry_bits(a + b)
    return _cond_sub_p(ctx, t)


def sub(ctx: FieldCtx, a, b):
    # a + (p - b): both < p so p - b has no borrow issues
    pb, _ = _sub_limbs(ctx.p_limbs, b)
    return add(ctx, a, pb)


def neg(ctx: FieldCtx, a):
    pb, _ = _sub_limbs(ctx.p_limbs, a)
    # p - 0 = p must normalize to 0
    is_zero = jnp.all(a == 0, axis=-1, keepdims=True)
    return jnp.where(is_zero, jnp.zeros_like(a), _cond_sub_p(ctx, pb))


def _mont_mul_cios(ctx: FieldCtx, a, b):
    """Montgomery product a*b*R^{-1} mod p: 16 CIOS rounds as a lax.scan,
    then one carry pass and one conditional subtraction of p, neither a
    loop.

    Each round is a fully vectorized multiply-accumulate over the batch; the
    scan keeps the traced graph small (an unrolled version is ~300 HLO ops per
    multiply, which made circuit-sized graphs take minutes to compile). Written
    scatter-free: shifted adds via concatenate. The rounds leave limbs up
    to ~2^24 unresolved, which is what `_carry_propagate` takes."""
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape)
    bT = jnp.moveaxis(jnp.broadcast_to(b, shape), -1, 0)  # [16, ...]
    p_limbs = ctx.p_limbs
    n0 = ctx.n0inv16
    z1 = jnp.zeros(shape[:-1] + (1,), dtype=jnp.uint32)

    def rnd(t, bi):
        prod = a * bi[..., None]          # [..., 16], each < 2^32
        t = (t
             + jnp.concatenate([prod & MASK, z1], axis=-1)
             + jnp.concatenate([z1, prod >> 16], axis=-1))
        m = (t[..., 0] * n0) & MASK
        q = p_limbs * m[..., None]
        t = (t
             + jnp.concatenate([q & MASK, z1], axis=-1)
             + jnp.concatenate([z1, q >> 16], axis=-1))
        # t[...,0] now ≡ 0 mod 2^16; shift down one limb
        carry = t[..., 0:1] >> 16
        t = jnp.concatenate([t[..., 1:2] + carry, t[..., 2:], z1], axis=-1)
        return t, None

    t0 = jnp.zeros(shape[:-1] + (NLIMBS + 1,), dtype=jnp.uint32)
    t, _ = jax.lax.scan(rnd, t0, bT)
    res, _top = _carry_propagate(t[..., :NLIMBS])
    # Montgomery guarantees result < 2p for p < R/4 (ours is), so top == 0
    return _cond_sub_p(ctx, res)


_USE_MXU = False


def mont_mul(ctx: FieldCtx, a, b):
    """Montgomery product dispatcher: CIOS scan by default, the MXU int8-limb
    matmul formulation (`field_mxu.mont_mul`, SURVEY.md §7 hard part 2) when
    `enable_mxu()` has been called. The flag is read at TRACE time, so a
    `from field_ops import mont_mul` binding still follows later swaps;
    executables compiled before the swap keep the implementation they traced
    (re-jit to pick up the new one)."""
    if _USE_MXU:
        from . import field_mxu
        return field_mxu.mont_mul(ctx, a, b)
    return _mont_mul_cios(ctx, a, b)


def enable_mxu(on: bool = True):
    """Route `mont_mul` through the MXU formulation (see dispatcher above).
    Auto-enabled when SPECTRE_FIELD_IMPL=mxu."""
    global _USE_MXU
    _USE_MXU = bool(on)


if __import__("os").environ.get("SPECTRE_FIELD_IMPL") == "mxu":
    enable_mxu()


def mont_sqr(ctx: FieldCtx, a):
    return mont_mul(ctx, a, a)


def to_mont(ctx: FieldCtx, a):
    return mont_mul(ctx, a, ctx.r2)


def from_mont(ctx: FieldCtx, a):
    one = jnp.zeros_like(a).at[..., 0].set(1)
    return mont_mul(ctx, a, one)


def mont_pow(ctx: FieldCtx, a, e: int, max_unroll: int = 24):
    """a^e for a host-known integer exponent.

    Short exponents unroll (fast, fully fused); long ones (e.g. Fermat
    inversion, 254 bits) run as a lax.fori_loop over a constant bit array to
    keep the traced graph small — an unrolled 254-bit ladder is ~400 chained
    mont_muls and makes XLA compile times explode."""
    if e < 0:
        raise ValueError("negative exponent")
    if e == 0:
        return jnp.broadcast_to(ctx.one_mont, a.shape)
    nbits = e.bit_length()
    if nbits <= max_unroll:
        result = None
        base = a
        while e:
            if e & 1:
                result = base if result is None else mont_mul(ctx, result, base)
            e >>= 1
            if e:
                base = mont_sqr(ctx, base)
        return result
    bits = jnp.asarray([(e >> i) & 1 for i in range(nbits)], dtype=jnp.uint32)

    def body(i, carry):
        result, base = carry
        mult = mont_mul(ctx, result, base)
        result = jnp.where((bits[i] == 1)[..., None], mult, result)
        base = mont_sqr(ctx, base)
        return (result, base)

    result0 = jnp.broadcast_to(ctx.one_mont, a.shape)
    result, _ = jax.lax.fori_loop(0, nbits, body, (result0, a))
    return result


def inv(ctx: FieldCtx, a):
    """Batched inversion via Fermat (a^(p-2)); inv(0) = 0."""
    return mont_pow(ctx, a, ctx.p - 2)


def limb_digits(scalars, w, c: int):
    """Extract window-w c-bit digits from [n, L] 16-bit limb tensors.

    Width-generic (L = 16 full scalars, L = 8 GLV half-scalars): the limb
    count comes from the tensor, not a module constant. w may be a traced
    int32 (used inside lax loops). Branchless across limb boundaries: a
    digit spans at most 2 limbs for c <= 16. Windows past the top limb read
    as zero (the padded-window idiom in parallel.sharded_msm relies on it)."""
    nlimbs = scalars.shape[-1]
    off = w * c
    limb_idx = off // 16
    shift = off % 16
    in_range = limb_idx < nlimbs
    col = jnp.take(scalars, jnp.minimum(limb_idx, nlimbs - 1), axis=1)
    col = jnp.where(in_range, col, 0)
    nxt = jnp.take(scalars, jnp.minimum(limb_idx + 1, nlimbs - 1), axis=1)
    lo = col >> shift
    hi = jnp.where(shift > 0, nxt << (16 - shift), 0)
    hi = jnp.where(limb_idx + 1 < nlimbs, hi, 0)
    return ((lo | hi) & ((1 << c) - 1)).astype(jnp.int32)


def is_zero(a):
    return jnp.all(a == 0, axis=-1)


def select(mask, a, b):
    """mask ? a : b, mask shaped [...] (no limb axis)."""
    return jnp.where(mask[..., None], a, b)


def eq(a, b):
    return jnp.all(a == b, axis=-1)


def mul_const(ctx: FieldCtx, a, c_mont):
    """Multiply by a broadcast constant already in Montgomery form."""
    return mont_mul(ctx, a, jnp.broadcast_to(c_mont, a.shape))
