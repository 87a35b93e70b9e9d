"""ctypes wrapper over libspectre_host.so with numpy limb interop.

Boundary convention (matches spectre_host.cc): field elements are 4 little-
endian uint64 limbs, standard (non-Montgomery) form; affine points are 8 limbs
(x||y) with (0,0) = infinity.
"""

from __future__ import annotations

import ctypes
import os
import subprocess

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libspectre_host.so")

FQ = 0
FR = 1


def _build_if_needed() -> bool:
    src = os.path.join(_DIR, "src", "spectre_host.cc")
    if os.path.exists(_SO):
        if not os.path.exists(src) or os.path.getmtime(_SO) >= os.path.getmtime(src):
            return True  # prebuilt .so without sources is fine
    try:
        subprocess.run(["make", "-C", _DIR], check=True, capture_output=True)
        return True
    except Exception:
        return False


class HostLib:
    _instance = None

    def __new__(cls):
        if cls._instance is None:
            if not _build_if_needed():
                raise RuntimeError("libspectre_host.so missing and build failed")
            lib = ctypes.CDLL(_SO)
            u64p = ctypes.POINTER(ctypes.c_uint64)
            lib.spectre_init.restype = None
            for name in ("fp_mul_batch", "fp_add_batch", "fp_sub_batch"):
                fn = getattr(lib, name)
                fn.argtypes = [ctypes.c_int, u64p, u64p, u64p, ctypes.c_size_t]
                fn.restype = None
            lib.fp_inv_batch.argtypes = [ctypes.c_int, u64p, u64p, ctypes.c_size_t]
            lib.fp_inv_batch.restype = None
            lib.fp_pow_single.argtypes = [ctypes.c_int, u64p, u64p, u64p]
            lib.fp_pow_single.restype = None
            lib.fr_ntt.argtypes = [u64p, ctypes.c_size_t, u64p]
            lib.fr_ntt.restype = None
            lib.g1_msm.argtypes = [u64p, u64p, ctypes.c_size_t, ctypes.c_int,
                                   u64p, ctypes.POINTER(ctypes.c_int)]
            lib.g1_msm.restype = None
            lib.g1_add_affine_batch.argtypes = [u64p, u64p, u64p, ctypes.c_size_t]
            lib.g1_add_affine_batch.restype = None
            lib.g1_scalar_powers.argtypes = [u64p, u64p, ctypes.c_size_t, u64p]
            lib.g1_scalar_powers.restype = None
            lib.g1_fixed_base_mul.argtypes = [u64p, u64p, ctypes.c_size_t, u64p]
            lib.g1_fixed_base_mul.restype = None
            lib.g1_fft.argtypes = [u64p, ctypes.c_size_t, u64p, u64p,
                                   ctypes.c_int]
            lib.g1_fft.restype = None
            lib.fp_horner.argtypes = [ctypes.c_int, u64p, u64p, u64p, ctypes.c_size_t]
            lib.fp_horner.restype = None
            lib.fp_sum.argtypes = [ctypes.c_int, u64p, u64p, ctypes.c_size_t]
            lib.fp_sum.restype = None
            lib.fp_scale_batch.argtypes = [ctypes.c_int, u64p, u64p, u64p, ctypes.c_size_t]
            lib.fp_scale_batch.restype = None
            lib.fp_add_scalar_batch.argtypes = [ctypes.c_int, u64p, u64p, u64p, ctypes.c_size_t]
            lib.fp_add_scalar_batch.restype = None
            lib.fp_axpy_batch.argtypes = [ctypes.c_int, u64p, u64p, u64p, u64p, ctypes.c_size_t]
            lib.fp_axpy_batch.restype = None
            lib.fp_powers.argtypes = [ctypes.c_int, u64p, u64p, ctypes.c_size_t]
            lib.fp_powers.restype = None
            lib.fp_prefix_prod.argtypes = [ctypes.c_int, u64p, u64p, ctypes.c_size_t]
            lib.fp_prefix_prod.restype = None
            lib.spectre_init()
            inst = super().__new__(cls)
            inst.lib = lib
            cls._instance = inst
        return cls._instance


def available() -> bool:
    try:
        HostLib()
        return True
    except Exception:  # missing sources, corrupt .so, failed build, ...
        return False


def _u64p(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


# ---------------------------------------------------------------------------
# int <-> limb conversion
# ---------------------------------------------------------------------------

def ints_to_limbs(vals, nlimbs: int = 4) -> np.ndarray:
    """list[int] -> [n, nlimbs] uint64 little-endian limb array (bulk bytes
    round-trip: int.to_bytes is C-speed, the per-limb shift loop was not)."""
    nbytes = 8 * nlimbs
    buf = b"".join(int(v).to_bytes(nbytes, "little") for v in vals)
    return np.frombuffer(buf, dtype="<u8").reshape(len(vals), nlimbs).astype(
        np.uint64, copy=True)


def limbs_to_ints(arr: np.ndarray) -> list:
    arr = np.ascontiguousarray(arr, dtype=np.uint64)
    n, nl = arr.shape
    buf = arr.astype("<u8", copy=False).tobytes()
    w = 8 * nl
    return [int.from_bytes(buf[i * w:(i + 1) * w], "little") for i in range(n)]


def points_to_limbs(points) -> np.ndarray:
    """list of affine (x, y) field-elem tuples or None -> [n, 8] uint64."""
    flat = []
    for pt in points:
        if pt is None:
            flat.extend([0, 0])
        else:
            flat.extend([int(pt[0]), int(pt[1])])
    xs = ints_to_limbs(flat)
    return xs.reshape(len(points), 8)


def limbs_to_points(arr: np.ndarray) -> list:
    """[n, 8] uint64 -> list of affine (x:int, y:int) or None for (0, 0):
    `points_to_limbs`' inverse."""
    flat = limbs_to_ints(np.ascontiguousarray(arr).reshape(-1, 4))
    return [None if x == 0 and y == 0 else (x, y)
            for x, y in zip(flat[0::2], flat[1::2])]


# ---------------------------------------------------------------------------
# ops
# ---------------------------------------------------------------------------

def _binop(name: str, field: int, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = HostLib().lib
    a = np.ascontiguousarray(a, dtype=np.uint64)
    b = np.ascontiguousarray(b, dtype=np.uint64)
    assert a.shape == b.shape and a.shape[1] == 4
    out = np.empty_like(a)
    getattr(lib, name)(field, _u64p(a), _u64p(b), _u64p(out), a.shape[0])
    return out


def fp_mul_batch(field: int, a, b):
    return _binop("fp_mul_batch", field, a, b)


def fp_add_batch(field: int, a, b):
    return _binop("fp_add_batch", field, a, b)


def fp_sub_batch(field: int, a, b):
    return _binop("fp_sub_batch", field, a, b)


def fp_inv_batch(field: int, a) -> np.ndarray:
    lib = HostLib().lib
    a = np.ascontiguousarray(a, dtype=np.uint64)
    assert a.ndim == 2 and a.shape[1] == 4
    out = np.empty_like(a)
    lib.fp_inv_batch(field, _u64p(a), _u64p(out), a.shape[0])
    return out


def fr_ntt(data: np.ndarray, omega: int) -> np.ndarray:
    """NTT of a C-contiguous uint64 [n, 4] limb array (n a power of 2).

    Transforms in place and returns the SAME array. Rejects inputs that would
    silently be copied (non-contiguous / wrong dtype), since the caller would
    otherwise keep an untransformed buffer."""
    lib = HostLib().lib
    assert isinstance(data, np.ndarray) and data.dtype == np.uint64 \
        and data.flags["C_CONTIGUOUS"], "fr_ntt requires a C-contiguous uint64 array"
    assert data.ndim == 2 and data.shape[1] == 4
    n = data.shape[0]
    logn = n.bit_length() - 1
    assert 1 << logn == n
    om = ints_to_limbs([omega])
    lib.fr_ntt(_u64p(data), logn, _u64p(om))
    return data


def g1_msm(points: np.ndarray, scalars: np.ndarray, nthreads: int = 1):
    """points [n,8], scalars [n,4] -> affine (x:int, y:int) or None."""
    lib = HostLib().lib
    points = np.ascontiguousarray(points, dtype=np.uint64)
    scalars = np.ascontiguousarray(scalars, dtype=np.uint64)
    n = points.shape[0]
    assert scalars.shape == (n, 4) and points.shape == (n, 8)
    out = np.zeros(8, dtype=np.uint64)
    inf = ctypes.c_int(0)
    lib.g1_msm(_u64p(points), _u64p(scalars), n, nthreads, _u64p(out),
               ctypes.byref(inf))
    if inf.value:
        return None
    x = sum(int(out[j]) << (64 * j) for j in range(4))
    y = sum(int(out[4 + j]) << (64 * j) for j in range(4))
    return (x, y)


def g1_add_affine_batch(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = HostLib().lib
    a = np.ascontiguousarray(a, dtype=np.uint64)
    b = np.ascontiguousarray(b, dtype=np.uint64)
    assert a.shape == b.shape and a.shape[1] == 8
    out = np.empty_like(a)
    lib.g1_add_affine_batch(_u64p(a), _u64p(b), _u64p(out), a.shape[0])
    return out


def g1_scalar_powers(g, tau: int, n: int) -> np.ndarray:
    """[n, 8] limbs: tau^i * g for i in [0, n). g = affine (x, y) ints."""
    lib = HostLib().lib
    gl = ints_to_limbs([int(g[0]), int(g[1])]).reshape(8)
    tl = ints_to_limbs([tau]).reshape(4)
    out = np.zeros((n, 8), dtype=np.uint64)
    lib.g1_scalar_powers(_u64p(gl), _u64p(tl), n, _u64p(out))
    return out


def g1_fixed_base_mul(g, scalars: np.ndarray) -> np.ndarray:
    """[n, 8] limbs: scalars[i] * g for [n, 4] standard-form scalars, through
    `g1_scalar_powers`' fixed-base table. g = affine (x, y) ints."""
    lib = HostLib().lib
    gl = ints_to_limbs([int(g[0]), int(g[1])]).reshape(8)
    scalars = np.ascontiguousarray(scalars, dtype=np.uint64)
    n = scalars.shape[0]
    assert scalars.shape == (n, 4)
    out = np.zeros((n, 8), dtype=np.uint64)
    lib.g1_fixed_base_mul(_u64p(gl), _u64p(scalars), n, _u64p(out))
    return out


def g1_fft(points: np.ndarray, omega: int, scale: int | None = None,
           nthreads: int | None = None) -> np.ndarray:
    """FFT over the group: [n, 8] affine points P_j (n a power of two) ->
    new [n, 8] array of sum_j omega^(i j) P_j, every point then multiplied
    by `scale` if given. With omega^-1 and 1 / n: the inverse transform."""
    lib = HostLib().lib
    out = np.array(points, dtype=np.uint64, order="C")
    n = out.shape[0]
    logn = n.bit_length() - 1
    assert out.shape == (n, 8) and 1 << logn == n
    sc = None if scale is None else ints_to_limbs([scale])
    if nthreads is None:
        nthreads = min(8, os.cpu_count() or 1)
    lib.g1_fft(_u64p(out), logn, _u64p(ints_to_limbs([omega])),
               None if sc is None else _u64p(sc), nthreads)
    return out


def fp_scale_batch(field: int, a: np.ndarray, s: int) -> np.ndarray:
    lib = HostLib().lib
    a = np.ascontiguousarray(a, dtype=np.uint64)
    assert a.ndim == 2 and a.shape[1] == 4
    sl = ints_to_limbs([s]).reshape(4)
    out = np.empty_like(a)
    lib.fp_scale_batch(field, _u64p(a), _u64p(sl), _u64p(out), a.shape[0])
    return out


def fp_add_scalar_batch(field: int, a: np.ndarray, s: int) -> np.ndarray:
    lib = HostLib().lib
    a = np.ascontiguousarray(a, dtype=np.uint64)
    assert a.ndim == 2 and a.shape[1] == 4
    sl = ints_to_limbs([s]).reshape(4)
    out = np.empty_like(a)
    lib.fp_add_scalar_batch(field, _u64p(a), _u64p(sl), _u64p(out), a.shape[0])
    return out


def fp_axpy_batch(field: int, a: np.ndarray, s: int, b: np.ndarray) -> np.ndarray:
    """out = a*s + b elementwise (one pass)."""
    lib = HostLib().lib
    a = np.ascontiguousarray(a, dtype=np.uint64)
    b = np.ascontiguousarray(b, dtype=np.uint64)
    assert a.shape == b.shape and a.ndim == 2 and a.shape[1] == 4
    sl = ints_to_limbs([s]).reshape(4)
    out = np.empty_like(a)
    lib.fp_axpy_batch(field, _u64p(a), _u64p(sl), _u64p(b), _u64p(out), a.shape[0])
    return out


def fp_powers(field: int, x: int, n: int) -> np.ndarray:
    lib = HostLib().lib
    xl = ints_to_limbs([x]).reshape(4)
    out = np.zeros((n, 4), dtype=np.uint64)
    lib.fp_powers(field, _u64p(xl), _u64p(out), n)
    return out


def fp_prefix_prod(field: int, a: np.ndarray) -> np.ndarray:
    lib = HostLib().lib
    a = np.ascontiguousarray(a, dtype=np.uint64)
    assert a.ndim == 2 and a.shape[1] == 4
    out = np.empty_like(a)
    lib.fp_prefix_prod(field, _u64p(a), _u64p(out), a.shape[0])
    return out


def fp_horner(field: int, a: np.ndarray, x: int) -> int:
    """Evaluate sum a[i] x^i (coefficients little-index-first)."""
    lib = HostLib().lib
    a = np.ascontiguousarray(a, dtype=np.uint64)
    assert a.ndim == 2 and a.shape[1] == 4
    xl = ints_to_limbs([x]).reshape(4)
    out = np.zeros(4, dtype=np.uint64)
    lib.fp_horner(field, _u64p(a), _u64p(xl), _u64p(out), a.shape[0])
    return sum(int(out[j]) << (64 * j) for j in range(4))


def fp_sum(field: int, a: np.ndarray) -> int:
    lib = HostLib().lib
    a = np.ascontiguousarray(a, dtype=np.uint64)
    assert a.ndim == 2 and a.shape[1] == 4
    out = np.zeros(4, dtype=np.uint64)
    lib.fp_sum(field, _u64p(a), _u64p(out), a.shape[0])
    return sum(int(out[j]) << (64 * j) for j in range(4))
