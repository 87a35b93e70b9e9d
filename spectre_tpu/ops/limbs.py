"""Conversion between Python ints, the native lib's 4x64 limbs and the
device's 16-bit limb tensors.

Device convention: a 256-bit value is [..., 16] uint32, little-endian 16-bit
limbs (each entry < 2^16); every kernel computes on that form.

Wire format, host to device, of the quotient's columns and the NTT kinds'
inputs: the [..., 4] uint64 rows the native C++ lib holds, viewed as
[..., 8] uint32 (`pack_u64limbs`: no copy, 32 bytes a field element, x64
stays off) and unpadded; the device splits each word into its two 16-bit
limbs and appends the zero rows a longer domain needs (`split_limbs16`, a
small program of its own). The MSM scalars and everything that comes DOWN
still cross as [..., 16] uint32, 64 bytes a field element, split and joined
on the host (`u64limbs_to_u16limbs`, `u16limbs_to_u64limbs`).
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

NLIMBS = 16
LIMB_BITS = 16
LIMB_MASK = (1 << LIMB_BITS) - 1


def ints_to_limbs16(vals) -> np.ndarray:
    """Iterable of ints -> [n, 16] uint32 (16-bit limbs, little-endian)."""
    vals = list(vals)
    out = np.zeros((len(vals), NLIMBS), dtype=np.uint32)
    for i, v in enumerate(vals):
        v = int(v)
        for j in range(NLIMBS):
            out[i, j] = (v >> (LIMB_BITS * j)) & LIMB_MASK
    return out


def limbs16_to_ints(arr: np.ndarray) -> list[int]:
    """[..., 16] limb array -> list of ints (leading axes flattened)."""
    arr = np.asarray(arr, dtype=np.uint64).reshape(-1, NLIMBS)
    return [sum(int(row[j]) << (LIMB_BITS * j) for j in range(NLIMBS)) for row in arr]


def int_to_limbs16(v: int) -> np.ndarray:
    return ints_to_limbs16([v])[0]


def u64limbs_to_u16limbs(arr: np.ndarray) -> np.ndarray:
    """[n, 4] uint64 (native lib format) -> [n, 16] uint32 16-bit limbs."""
    arr = np.asarray(arr, dtype=np.uint64)
    n = arr.shape[0]
    out = np.zeros((n, NLIMBS), dtype=np.uint32)
    for j in range(4):
        limb = arr[:, j]
        for k in range(4):
            out[:, 4 * j + k] = (limb >> np.uint64(16 * k)).astype(np.uint64) & np.uint64(0xFFFF)
    return out


def u16limbs_to_u64limbs(arr: np.ndarray) -> np.ndarray:
    """[n, 16] uint32 16-bit limbs -> [n, 4] uint64 (native lib format)."""
    arr = np.asarray(arr, dtype=np.uint64)
    n = arr.shape[0]
    out = np.zeros((n, 4), dtype=np.uint64)
    for j in range(4):
        acc = np.zeros(n, dtype=np.uint64)
        for k in range(4):
            acc |= (arr[:, 4 * j + k] & np.uint64(0xFFFF)) << np.uint64(16 * k)
        out[:, j] = acc
    return out


def pack_u64limbs(arr: np.ndarray) -> np.ndarray:
    """[..., 4] uint64 (native lib format) -> the same bytes as [..., 8]
    uint32, a view where `arr` is contiguous: what `split_limbs16` takes."""
    if sys.byteorder != "little":
        raise NotImplementedError("the packed wire format is the host's "
                                  "little-endian uint64 rows")
    return np.ascontiguousarray(arr, dtype=np.uint64).view(np.uint32)


@functools.partial(jax.jit, static_argnums=1)
def split_limbs16(packed, n_out: int | None = None):
    """Device half of the wire format: [..., n, 8] uint32 (`pack_u64limbs`)
    -> [..., n_out or n, 16] uint32 16-bit limbs, limb for limb what
    `u64limbs_to_u16limbs` gives for the rows zero-padded to `n_out`. One
    small program a shape, `split_limbs16` in a device trace; elementwise
    along every leading axis, so a batch-sharded stack stays where it is."""
    lo = packed & jnp.uint32(LIMB_MASK)
    hi = packed >> jnp.uint32(LIMB_BITS)
    out = jnp.stack([lo, hi], axis=-1).reshape(packed.shape[:-1] + (NLIMBS,))
    if n_out is not None:
        pad = [(0, 0)] * out.ndim
        pad[-2] = (0, n_out - out.shape[-2])
        out = jnp.pad(out, pad)
    return out
