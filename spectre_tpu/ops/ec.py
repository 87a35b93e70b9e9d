"""Batched BN254 G1 arithmetic on limb tensors (device side of N2).

Points are homogeneous projective (X:Y:Z) limb tensors [..., 3, 16] in
Montgomery form, with infinity = (0:1:0). Addition uses the Renes–Costello–
Batina COMPLETE formulas for j-invariant-0 curves (alg. 7: 12M + 2 small-const
M, branchless): one uniform vectorized formula covers generic add, doubling,
inverses and infinity — no data-dependent control flow, which is exactly what
the TPU/XLA execution model wants (the reference's CPU Pippenger branches per
point; branching is the wrong shape for SIMD lanes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.tracing import on_device, span
from . import field_ops as F


def _fq():
    return F.fq_ctx()


def encode_points(points) -> jax.Array:
    """Host: list of affine (x, y) | None -> [n, 3, 16] projective Montgomery."""
    ctx = _fq()
    xs, ys, zs = [], [], []
    for pt in points:
        if pt is None:
            xs.append(0), ys.append(1), zs.append(0)
        else:
            xs.append(int(pt[0])), ys.append(int(pt[1])), zs.append(1)
    return jnp.stack([ctx.encode(xs), ctx.encode(ys), ctx.encode(zs)], axis=-2)


# module-level jitted entry point (trace-cache hygiene lint root)
TRACE_JIT_ROOTS = ("_affine_mont",)


@jax.jit
def _affine_mont(arr):
    """[m, 3, 16] projective Montgomery -> (x/z, y/z) Montgomery limbs.

    Jitted so it is ONE stable program per m: run eagerly, the Fermat-
    inversion loop and each CIOS multiply are top-level `lax.scan`s whose
    bodies retrace into fresh jaxprs on every call, and every decoded MSM
    result recompiles a handful of programs (~600 backend compiles in one
    tiny keygen; a warm prove never reaches `compile.count == 0`)."""
    ctx = _fq()
    zinv = F.inv(ctx, arr[:, 2])
    return (F.mont_mul(ctx, arr[:, 0], zinv),
            F.mont_mul(ctx, arr[:, 1], zinv))


def affine_points(arr):
    """Enqueue the affine conversion of [m, 3, 16] projective Montgomery
    points and return the device's (x, y, z) limbs, unread."""
    arr = jnp.asarray(arr).reshape(-1, 3, F.NLIMBS)
    xm, ym = _affine_mont(arr)
    return xm, ym, arr[:, 2]


def read_points(affine, call: str, keep: int | None = None) -> list:
    """`affine_points`' three arrays -> list of affine (x:int, y:int) |
    None for the first `keep` points (all, by default; a fixed-width
    batch's padding is read with the rest, 192 bytes a point, and
    dropped), under the stage spans `wait` (the three reads; the first is
    the one that blocks) and `decode` (the host's Montgomery decoding) of
    `call`."""
    ctx = _fq()
    xm, ym, z = affine
    with span(call + "/wait", bytes=xm.nbytes + ym.nbytes + z.nbytes):
        xm, ym, z = (np.asarray(a)[:keep] for a in (xm, ym, z))
    with span(call + "/decode"):
        xs, ys, z_int = ctx.decode(xm), ctx.decode(ym), ctx.decode(z)
        return [None if z == 0 else (x, y)
                for x, y, z in zip(xs, ys, z_int)]


def decode_points(arr, call: str = "ec/decode_points") -> list:
    """Device projective -> list of affine (x:int, y:int) | None.

    Three stage spans named for `call` (the backend passes its own call's
    name; observability/tracing.py): `dispatch` enqueues the affine
    conversion, then `read_points`' `wait` and `decode`."""
    with span(call + "/dispatch"):
        affine = affine_points(arr)
        on_device(affine)
    return read_points(affine, call)


def inf_point(shape=()) -> jax.Array:
    """Projective infinity (0:1:0) broadcast to [..., 3, 16]."""
    ctx = _fq()
    pt = jnp.stack([ctx.zero, ctx.one_mont, ctx.zero], axis=0)
    return jnp.broadcast_to(pt, tuple(shape) + (3, F.NLIMBS))


def padd(p, q):
    """Complete projective add, a=0, b=3 (RCB alg. 7). p, q: [..., 3, 16].

    The 12 field multiplies are batched into TWO stacked mont_mul calls (the
    formula has two dependency layers of muls); adds/subs are likewise stacked
    (11 calls). This matters: a mont_mul's CIOS rounds are a 16-step lax.scan,
    every step a sequence of device programs of its own, and XLA compile time
    scales with scan count, so 2 big scans beat 12 small ones — runtime also
    improves (wider batches per kernel). The two are the only loops of an
    addition: add / sub and the multiplications' tails resolve their carries
    in one pass (field_ops._resolve; tests/test_msm_modes.py pins the count,
    which was 39 scans = 624 sequential steps while they went limb by
    limb)."""
    ctx = _fq()
    add = lambda a, b: F.add(ctx, a, b)       # noqa: E731
    sub = lambda a, b: F.sub(ctx, a, b)       # noqa: E731
    x1, y1, z1 = p[..., 0, :], p[..., 1, :], p[..., 2, :]
    x2, y2, z2 = q[..., 0, :], q[..., 1, :], q[..., 2, :]

    # pre-sums, stacked: [x1+y1, y1+z1, x1+z1] and same for q
    s1 = add(jnp.stack([x1, y1, x1]), jnp.stack([y1, z1, z1]))
    s2 = add(jnp.stack([x2, y2, x2]), jnp.stack([y2, z2, z2]))

    # mul layer 1: t0=x1x2, t1=y1y2, t2=z1z2, m3=(x1+y1)(x2+y2),
    #              m4=(y1+z1)(y2+z2), m5=(x1+z1)(x2+z2)
    la = jnp.concatenate([jnp.stack([x1, y1, z1]), s1], axis=0)
    lb = jnp.concatenate([jnp.stack([x2, y2, z2]), s2], axis=0)
    t0, t1, t2, m3, m4, m5 = F.mont_mul(ctx, la, lb)

    # cross terms, stacked subtract: t3 = x1y2+x2y1, t4 = y1z2+y2z1, ycross = x1z2+x2z1
    sums = add(jnp.stack([t0, t1, t0]), jnp.stack([t1, t2, t2]))
    t3, t4, ycross = sub(jnp.stack([m3, m4, m5]), sums)

    t0_3 = add(add(t0, t0), t0)               # 3 x1x2
    # b3 = 3b = 9 multiples of t2 and ycross via stacked add chain
    v = jnp.stack([t2, ycross])
    v2 = add(v, v)
    v8 = add(v2, v2)
    v8 = add(v8, v8)
    b3t2, b3y = add(v8, v)

    z3 = add(t1, b3t2)
    t1m = sub(t1, b3t2)

    # mul layer 2: x3a=t4*b3y, x3b=t3*t1m, y3a=b3y*t0_3, y3b=t1m*z3,
    #              z3a=t0_3*t3, z3b=z3*t4
    la2 = jnp.stack([t4, t3, b3y, t1m, t0_3, z3])
    lb2 = jnp.stack([b3y, t1m, t0_3, z3, t3, t4])
    x3a, x3b, y3a, y3b, z3a, z3b = F.mont_mul(ctx, la2, lb2)

    res = jnp.stack([sub(x3b, x3a), add(y3b, y3a), add(z3b, z3a)], axis=-2)
    return res


def pdbl(p):
    """Doubling via the complete add (could specialize later; complete add
    already handles it — kept for call-site clarity)."""
    return padd(p, p)


def pneg(p):
    ctx = _fq()
    return jnp.stack([p[..., 0, :], F.neg(ctx, p[..., 1, :]), p[..., 2, :]], axis=-2)


def cneg(mask, p):
    """mask ? -p : p with mask shaped [...] (no point/limb axes).

    One field negation + select — the device half of signed-digit /
    GLV sign handling (a negated point replaces 2^(c-1)..2^c bucket work,
    and a negated half-scalar replaces ~127 doublings).

    Infinity caveat: -(0:1:0) = (0:p-1:0), a NON-CANONICAL representative
    of the same point (Z = 0). That is fine everywhere cneg output feeds
    `padd` — the complete formulas treat any Z = 0 input as the identity —
    but it means bucket/accumulator states are only representative-equal,
    never bit-equal, once a masked infinity has passed through. Compare
    via decode_points (or a Z-normalizing hash), not raw limbs."""
    return select_point(mask, pneg(p), p)


@functools.cache
def _beta_mont():
    """GLV endomorphism constant beta (cube root of unity in Fq),
    Montgomery-encoded, as numpy (fresh embedded constant per trace)."""
    from . import glv
    return _fq().encode([glv.beta()])[0]


def endo(p):
    """phi(X:Y:Z) = (beta*X : Y : Z), the GLV endomorphism, batched.

    Completeness note: phi maps E to itself (beta^3 = 1 so the curve
    equation is preserved) and fixes infinity (beta*0 = 0 keeps (0:1:0)),
    so phi images — like negated points, which also stay on E — remain
    inside the domain where the RCB complete formulas in `padd` are proven
    exception-free: a = 0, b = 3, ALL input pairs including doubling,
    inverses, and the identity. No new case analysis is introduced by the
    GLV/signed-digit paths."""
    ctx = _fq()
    bx = F.mul_const(ctx, p[..., 0, :], jnp.asarray(_beta_mont()))
    return jnp.stack([bx, p[..., 1, :], p[..., 2, :]], axis=-2)


def select_point(mask, a, b):
    """mask ? a : b with mask shaped [...] (no point/limb axes)."""
    return jnp.where(mask[..., None, None], a, b)


def is_inf(p):
    return F.is_zero(p[..., 2, :])
