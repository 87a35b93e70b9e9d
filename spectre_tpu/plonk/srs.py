"""KZG structured reference string (powers-of-tau), with file cache.

Reference parity: halo2-base `gen_srs` / PARAMS_DIR caching
(`util/circuit.rs` + SURVEY.md §5 checkpoint/resume). Production use consumes
a ceremony transcript; tests generate an INSECURE deterministic setup from a
seed (tau derived and then discarded — fine for testing, never for deployment).
"""

from __future__ import annotations

import hashlib
import os
import threading

import numpy as np

from ..fields import bn254
from ..native import host

R = bn254.R

PARAMS_DIR = os.environ.get("PARAMS_DIR", os.path.join(os.path.dirname(__file__), "..", "..", "params"))


def _base_digest(tag: bytes, k: int, base: np.ndarray) -> str:
    h = hashlib.blake2b(digest_size=16)
    h.update(tag)
    h.update(k.to_bytes(4, "little"))
    h.update(np.ascontiguousarray(base.astype("<u8")).tobytes())
    return h.hexdigest()


def lagrange_scalars(k: int, tau: int) -> np.ndarray:
    """[2^k, 4] limbs: L_i(tau) = (tau^n - 1) omega^i / (n (tau - omega^i)),
    the Lagrange polynomials of the domain of 2^k evaluated at tau (which is
    no n-th root of unity: a set-up whose tau were one would be broken)."""
    from .domain import get_domain
    n = 1 << k
    z = (pow(tau, n, R) - 1) % R
    assert z, "tau lies on the domain"
    omegas = host.fp_powers(host.FR, get_domain(k).omega, n)
    # n (tau - omega^i)
    den = host.fp_scale_batch(
        host.FR, host.fp_add_scalar_batch(
            host.FR, host.fp_scale_batch(host.FR, omegas, R - 1), tau), n)
    return host.fp_scale_batch(
        host.FR, host.fp_mul_batch(host.FR, omegas,
                                   host.fp_inv_batch(host.FR, den)), z)


class SRS:
    """g1_powers: [n, 8] u64 affine standard limbs (tau^i G); g1_lagrange:
    the same shape, L_i(tau) G for the domain of 2^k (a column's VALUES
    commit against it to the point its coefficients commit to against the
    powers; halo2's `ParamsKZG.g_lagrange`); g2 elements.

    The Lagrange base comes from tau where the set-up knows it
    (`unsafe_setup`), else on first use from the powers by the group's
    inverse FFT (the upstream's `g_to_lagrange`: seconds at k=14, minutes at
    k=18, once: `read` remembers the sibling file `<path>.lagrange` and the
    base is loaded from it or, once computed, kept there). The base of 2^k is
    no prefix of the base of 2^(k+1): a smaller SRS cut from a larger one's
    powers gets its own."""

    def __init__(self, k: int, g1_powers: np.ndarray, g2_gen, g2_tau,
                 g1_lagrange: np.ndarray | None = None):
        self.k = k
        self.n = 1 << k
        self.g1_powers = g1_powers
        self.g2_gen = g2_gen
        self.g2_tau = g2_tau
        self._g1_lagrange = g1_lagrange
        self._lagrange_path = None      # the sibling file, once read or written
        self._lock = threading.Lock()
        self._truncated: dict = {}      # k -> the SRS `truncate` cut
        self._digest = None
        self._lagrange_digest = None

    def digest(self) -> str:
        """Stable content digest of the G1 base (hex). Keys the fixed-base
        MSM table cache (ops.msm) across processes and re-encodings — two
        SRS objects loaded from the same ceremony share tables. Computed
        once (blake2b over the full power table: ~0.1 s at k=20)."""
        if self._digest is None:
            self._digest = _base_digest(b"SPTSRS02", self.k, self.g1_powers)
        return self._digest

    def lagrange_digest(self) -> str:
        """`digest` of the Lagrange base: another base, another table."""
        if self._lagrange_digest is None:
            self._lagrange_digest = _base_digest(b"SPTLAG01", self.k,
                                                 self.g1_lagrange)
        return self._lagrange_digest

    @property
    def g1_lagrange(self) -> np.ndarray:
        """[n, 8] u64 affine: L_i(tau) G. One array for the object's life
        (the backend keeps its device copy by identity)."""
        with self._lock:
            if self._g1_lagrange is None:
                self._g1_lagrange = self._read_lagrange()
            if self._g1_lagrange is None:
                from .domain import get_domain
                self._g1_lagrange = host.g1_fft(
                    self.g1_powers[:self.n], get_domain(self.k).omega_inv,
                    scale=pow(self.n, -1, R))
                if self._lagrange_path:
                    self._write_lagrange(self._lagrange_path)
            return self._g1_lagrange

    @classmethod
    def unsafe_setup(cls, k: int, seed: bytes = b"spectre-tpu-test-srs") -> "SRS":
        """tau depends on the seed ONLY (not k): different-k setups from one
        seed share tau, so a small SRS is a prefix of a large one — the
        ceremony-transcript property the aggregation layer requires (the
        deferred pairing of an inner proof at k1 is checked by the outer
        layer against the SAME [tau]_2; reference: per-k params files
        truncated from one perpetual-powers-of-tau ceremony)."""
        tau = int.from_bytes(hashlib.sha256(seed).digest() * 2, "big") % R
        n = 1 << k
        gen = (int(bn254.G1_GEN[0]), int(bn254.G1_GEN[1]))
        g1p = host.g1_scalar_powers(gen, tau, n)
        g1l = host.g1_fixed_base_mul(gen, lagrange_scalars(k, tau))
        g2_tau = bn254.g2_curve.mul(bn254.G2_GEN, tau)
        return cls(k, g1p, bn254.G2_GEN, g2_tau, g1_lagrange=g1l)

    @classmethod
    def load_or_setup(cls, k: int, directory: str | None = None) -> "SRS":
        from ..utils import faults
        faults.check("srs.load")    # injection site (resilience tests)
        directory = directory or PARAMS_DIR
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, f"kzg_bn254_{k}.srs")
        if os.path.exists(path):
            return cls.read(path)
        # derive from a larger cached SRS when available (prefix property)
        for bigger in range(k + 1, 27):
            bp = os.path.join(directory, f"kzg_bn254_{bigger}.srs")
            if os.path.exists(bp):
                big = cls.read(bp)
                srs = cls(k, big.g1_powers[:1 << k].copy(), big.g2_gen, big.g2_tau)
                srs.write(path)
                return srs
        srs = cls.unsafe_setup(k)
        srs.write(path)
        return srs

    def truncate(self, k: int) -> "SRS":
        """The SRS of 2^k: a prefix of the powers, a Lagrange base of its
        own. One object a k, so that base is worked out once."""
        assert k <= self.k
        if k == self.k:
            return self
        with self._lock:
            if k not in self._truncated:
                self._truncated[k] = SRS(k, self.g1_powers[:1 << k],
                                         self.g2_gen, self.g2_tau)
            return self._truncated[k]

    # -- the Lagrange base's sibling file: header || k || g1 limbs --
    def _write_lagrange(self, path: str):
        from ..utils import artifacts
        artifacts._atomic_write(
            path, b"SPTLAG01" + self.k.to_bytes(4, "little")
            + self._g1_lagrange.astype("<u8").tobytes())
        artifacts.write_sidecar(path)

    def _read_lagrange(self):
        path = self._lagrange_path
        if not path or not os.path.exists(path):
            return None
        from ..utils import artifacts
        with open(path, "rb") as f:
            raw = f.read()
        artifacts.verify_sidecar(path, raw)
        if raw[:8] != b"SPTLAG01" \
                or int.from_bytes(raw[8:12], "little") != self.k \
                or len(raw) != 12 + 64 * self.n:
            raise ValueError(f"{path}: not the Lagrange base of k={self.k}")
        return np.frombuffer(raw[12:], dtype="<u8").reshape(
            self.n, 8).astype(np.uint64)

    # -- serialization: header || g1 limbs || g2 points (uncompressed BE) --
    def write(self, path: str):
        """The powers at `path` and, where the Lagrange base is at hand (a
        set-up from tau), the base beside them; an SRS that has not needed
        it yet writes it when it does."""
        self._lagrange_path = path + ".lagrange"
        if self._g1_lagrange is not None:
            self._write_lagrange(self._lagrange_path)
        with open(path, "wb") as f:
            f.write(b"SPTSRS02")
            f.write(self.k.to_bytes(4, "little"))
            f.write(self.g1_powers.astype("<u8").tobytes())
            f.write(bn254.g2_to_bytes(self.g2_gen))
            f.write(bn254.g2_to_bytes(self.g2_tau))
        # integrity sidecar (ISSUE 6): <path>.sha256 lets `read` detect a
        # bit-flipped params file as a typed ArtifactCorrupt at load time
        # instead of a deep keygen/prove blow-up hours later
        from ..utils import artifacts
        artifacts.write_sidecar(path)

    @classmethod
    def read(cls, path: str, verify: bool = True) -> "SRS":
        from ..utils import artifacts
        with open(path, "rb") as f:
            raw = f.read()
        if verify:
            # a MISSING sidecar stays loadable (pre-checksum params dirs);
            # a mismatching one refuses with a typed ArtifactCorrupt
            artifacts.verify_sidecar(path, raw)
        assert raw[:8] == b"SPTSRS02", \
            "bad/stale SRS file (tau derivation changed in SPTSRS02; delete the params dir)"
        k = int.from_bytes(raw[8:12], "little")
        n = 1 << k
        off = 12
        g1 = np.frombuffer(raw[off:off + n * 8 * 8],
                           dtype="<u8").reshape(n, 8).copy()
        off += n * 8 * 8
        g2_gen = bn254.g2_from_bytes(raw[off:off + 128])
        g2_tau = bn254.g2_from_bytes(raw[off + 128:off + 256])
        srs = cls(k, g1, g2_gen, g2_tau)
        srs._lagrange_path = path + ".lagrange"
        return srs
