"""Montgomery multiplication as MXU matmuls (int8-limb formulation).

SURVEY.md §7 hard part 2: the systolic array, not the VPU, is where TPU
FLOPs live — but bignum multiply is a *convolution* of limb vectors, which
is bilinear, not linear. The mapping used here:

    conv(a, b)[k] = sum_{i+j=k} a_i * b_j
                  = reshape(outer(a, b), [L*L]) @ S        (one matmul)

where `S` is the constant one-hot [L*L, 2L] matrix with S[(i,j), i+j] = 1.
The outer product is an elementwise broadcast multiply (VPU, O(L^2) int32
MACs per element); the REDUCTION — the O(L^2) accumulate that dominates the
schoolbook/CIOS op count — becomes a [N, L*L] @ [L*L, 2L] matmul with a
large batch dimension N: exactly the shape XLA tiles onto the MXU
(contraction 1024, output 64, M = batch). Limbs are 8-bit so every partial
product fits comfortably: max a_i*b_j = 255^2 < 2^16, column sums < L * 2^16
+ carries < 2^22 « int32.

One Montgomery product a*b*R^-1 (R = 2^256) is three such multiplies
(separated operand scanning, Montgomery's original form):

    t  = a * b                      (full 512-bit product)
    m  = (t mod R) * p' mod R       (low half only, p' = -p^-1 mod R)
    out = (t + m * p) / R           (full product + shift)

~3L^2 = 3072 8-bit MACs vs CIOS's 512 16-bit VPU MACs — more raw MACs, but
on MXU lanes instead of VPU lanes (v5e: 394 Tops int8 MXU vs ~4 Tops VPU),
so the formulation wins whenever the matmul actually lands on the MXU.
On CPU (XLA:CPU) the same graph is exact but slower than CIOS — this module
is therefore opt-in: set SPECTRE_FIELD_IMPL=mxu or call `enable()`
(what the CPU can check is exactness, which
`tests/test_ops.py::TestMxuField` pins; speed is a chip question).

Layout compatibility: public entry points take and return the SAME
[..., 16]-limb uint32 tensors as `field_ops` — conversion to/from the
internal [..., 32] 8-bit layout is two cheap vectorized bit ops.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import field_ops as F

L8 = 32          # 8-bit limbs per 256-bit value
MASK8 = np.uint32(0xFF)


@functools.cache
def conv_matrix(la: int, lb: int, out_cols: int) -> np.ndarray:
    """One-hot [la*lb, out_cols] reduction matrix: (i,j) -> column i+j.
    Truncating out_cols below la+lb-1 drops high columns (mod-2^(8*out)
    products for the Montgomery m step). Shared with the matmul-NTT short
    transform in ops/ntt.py, which convolves 33-limb reduction constants
    against 32-limb data."""
    S = np.zeros((la * lb, out_cols), dtype=np.int32)
    for i in range(la):
        for j in range(lb):
            k = i + j
            if k < out_cols:
                S[i * lb + j, k] = 1
    return S


def _conv_matrix(full: bool) -> np.ndarray:
    return conv_matrix(L8, L8, 2 * L8 if full else L8)


class MxuCtx:
    """Per-modulus constants in the 8-bit-limb domain."""

    def __init__(self, ctx: F.FieldCtx):
        self.base = ctx
        p = ctx.p
        self.p8 = np.array([(p >> (8 * i)) & 0xFF for i in range(L8)],
                           dtype=np.int32)
        pinv = (-pow(p, -1, 1 << 256)) % (1 << 256)   # p' = -p^-1 mod R
        self.pinv8 = np.array([(pinv >> (8 * i)) & 0xFF for i in range(L8)],
                              dtype=np.int32)


@functools.cache
def _mxu_ctx(name: str) -> MxuCtx:
    base = {"bn254_fr": F.fr_ctx, "bn254_fq": F.fq_ctx}[name]()
    return MxuCtx(base)


def _to8(a):
    """[..., 16] uint32 16-bit limbs -> [..., 32] int32 8-bit limbs."""
    lo = (a & MASK8).astype(jnp.int32)
    hi = ((a >> 8) & MASK8).astype(jnp.int32)
    return jnp.stack([lo, hi], axis=-1).reshape(*a.shape[:-1], L8)

def _from8(a8):
    """[..., 32] int32 8-bit limbs (< 2^8) -> [..., 16] uint32 16-bit limbs."""
    pairs = a8.reshape(*a8.shape[:-1], 16, 2).astype(jnp.uint32)
    return pairs[..., 0] | (pairs[..., 1] << 8)


def _carry8(t, out_limbs: int):
    """Carry-propagate a [..., k] int32 column tensor into `out_limbs` 8-bit
    limbs (little-endian), dropping any final carry overflowing out_limbs
    (callers size out_limbs so it never does)."""
    tT = jnp.moveaxis(t, -1, 0)

    def step(carry, ti):
        cur = ti + carry
        return cur >> 8, cur & jnp.int32(0xFF)

    carry, outs = jax.lax.scan(step, jnp.zeros_like(tT[0]), tT)
    outs = jnp.moveaxis(outs, 0, -1)
    k = outs.shape[-1]
    if k < out_limbs:
        # remaining carry extends into higher limbs
        ext = []
        for _ in range(out_limbs - k):
            ext.append(carry & 0xFF)
            carry = carry >> 8
        outs = jnp.concatenate([outs] + [e[..., None] for e in ext], axis=-1)
    return outs[..., :out_limbs]


def mul_columns(a8, b8, out_cols: int):
    """Raw column products via the one-hot matmul; no carries yet.
    a8: [..., la], b8: [..., lb] int32 (entries < 2^8). Returns
    [..., out_cols] int32 convolution columns."""
    la, lb = a8.shape[-1], b8.shape[-1]
    outer = a8[..., :, None] * b8[..., None, :]           # [..., la, lb] VPU
    flat = outer.reshape(*outer.shape[:-2], la * lb)
    S = conv_matrix(la, lb, out_cols)
    # [N, la*lb] @ [la*lb, out]: the MXU-shaped reduction
    return jax.lax.dot_general(
        flat, S, (((flat.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def _mul_columns(a8, b8, full: bool):
    return mul_columns(a8, b8, 2 * L8 if full else L8)


def mont_mul(ctx: F.FieldCtx, a, b):
    """Drop-in replacement for `field_ops.mont_mul` (same layout, same
    Montgomery form): 3 matmul-multiplies + carries."""
    mc = _mxu_ctx(ctx.name)
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a8 = _to8(jnp.broadcast_to(a, shape))
    b8 = _to8(jnp.broadcast_to(b, shape))

    # t = a * b, 64 columns; carried to 64 8-bit limbs
    t_cols = _mul_columns(a8, b8, full=True)
    t8 = _carry8(t_cols, 2 * L8)
    t_lo, t_hi = t8[..., :L8], t8[..., L8:]

    # m = t_lo * p' mod 2^256 (low-half product)
    m_cols = _mul_columns(t_lo, jnp.broadcast_to(mc.pinv8, t_lo.shape),
                          full=False)
    m8 = _carry8(m_cols, L8)

    # u = (t + m*p) / 2^256. Low half of t + m*p is 0 by construction; the
    # carry out of the low half is what must flow into the high half. Add
    # the low columns t_lo + (m*p)_lo, propagate, keep ONLY the carry.
    mp_cols = _mul_columns(m8, jnp.broadcast_to(mc.p8, m8.shape), full=True)
    low_sum = mp_cols[..., :L8] + t_lo
    lowT = jnp.moveaxis(low_sum, -1, 0)

    def step(carry, ti):
        cur = ti + carry
        return cur >> 8, cur & jnp.int32(0xFF)

    carry_low, _ = jax.lax.scan(step, jnp.zeros_like(lowT[0]), lowT)

    hi_cols = mp_cols[..., L8:] + t_hi
    hi_cols = hi_cols.at[..., 0].add(carry_low)
    # u = (t + m*p)/R < 2p < 2^255: 32 8-bit limbs suffice
    u8 = _carry8(hi_cols, L8)
    res16 = _from8(u8.astype(jnp.uint32))
    return F._cond_sub_p(ctx, res16)


def enabled() -> bool:
    import os
    return os.environ.get("SPECTRE_FIELD_IMPL") == "mxu"
