"""Key generation: fixed-poly commitments, permutation sigmas, query plan.

Reference parity: halo2 keygen_vk/keygen_pk via `AppCircuit::create_pk`
(`util/circuit.rs:119-137`). The query plan (which poly is opened at which
rotations) is the shared contract between prover and verifier.
"""

from __future__ import annotations

import hashlib
import os
from dataclasses import dataclass

from ..fields import bn254
from . import backend as B
from .constraint_system import (CircuitConfig, NUM_H_CHUNKS, build_sigma,
                                table_column)
from .domain import Domain
from .srs import SRS
from . import kzg

R = bn254.R

# rotation tag for the "last usable row" query used by permutation chunk links
ROT_LAST = "last"

# The kinds of the proving key's own columns (selectors, fixed, sigmas,
# tables, the SHA region's selectors and round constants): their extended
# forms are the same on every prove of a key, so both quotient paths keep
# them extended a key (`prover._quotient_host` on the host,
# `quotient_device` on the device), under `ext_cache_budget_bytes`.
KEY_KINDS = ("q", "fix", "sig", "tab", "shq", "shk")


def meminfo_total_bytes():
    try:
        with open("/proc/meminfo") as f:
            return int(f.readline().split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return None


def ext_cache_budget_bytes(memory_bytes=None) -> int:
    """Bytes a proving key's extended columns may keep resident, in the
    memory they live in: SPECTRE_EXT_CACHE_MB where set (a fraction of a MB
    allowed), else 15 % of `memory_bytes` (the device's `bytes_limit` for
    the device store), else min(16 GB, 15 % of RAM) (the host store, and a
    device that reports no limit). Past it, columns are extended a prove."""
    mb = os.environ.get("SPECTRE_EXT_CACHE_MB")
    if mb is not None:
        return int(float(mb) * (1 << 20))
    if memory_bytes:
        return int(memory_bytes * 0.15)
    total = meminfo_total_bytes()
    return 16 << 30 if total is None else min(16 << 30, int(total * 0.15))


@dataclass
class VerifyingKey:
    config: CircuitConfig
    selector_commits: list
    fixed_commits: list
    sigma_commits: list
    table_commits: list    # one per lookup-advice column (cfg.table_id(j))
    sha_selector_commits: list = None   # 7 region selectors (num_sha_slots)
    sha_k_commit: object = None         # round-constant column

    @property
    def domain(self) -> Domain:
        from .domain import get_domain
        return get_domain(self.config.k)

    def digest(self) -> bytes:
        h = hashlib.blake2b(digest_size=32)
        cfg = self.config
        h.update(repr((cfg.k, cfg.num_advice, cfg.num_lookup_advice, cfg.num_fixed,
                       cfg.lookup_bits, cfg.num_instance,
                       cfg.num_sha_slots)).encode())
        h.update(repr(cfg.lookup_tables).encode())
        for pt in (self.selector_commits + self.fixed_commits
                   + self.sigma_commits + self.table_commits
                   + (self.sha_selector_commits or [])
                   + ([self.sha_k_commit] if cfg.num_sha_slots else [])):
            h.update(bn254.g1_to_bytes(pt))
        return h.digest()

    def commitment_plan(self):
        """Ordered commitment keys as read from the proof stream, with the
        challenge boundaries: (keys, pre_beta_gamma, pre_y, pre_x). The
        SINGLE source for the verifier and the EVM codegen — any change to
        the prover's write order must land here."""
        cfg = self.config
        keys = []
        for j in range(cfg.num_advice):
            keys.append(("adv", j))
        for j in range(cfg.num_lookup_advice):
            keys.append(("ladv", j))
        for j in range(cfg.num_sha_bit):
            keys.append(("shb", j))
        for j in range(cfg.num_sha_word):
            keys.append(("shw", j))
        for j in range(cfg.num_lookup_advice):
            keys.append(("pA", j))
            keys.append(("pT", j))
        pre_bg = len(keys)
        for c in range(cfg.num_perm_chunks):
            keys.append(("pz", c))
        for j in range(cfg.num_lookup_advice):
            keys.append(("lz", j))
        pre_y = len(keys)
        for i in range(NUM_H_CHUNKS):
            keys.append(("h", i))
        return keys, pre_bg, pre_y, len(keys)

    def fixed_commitment_map(self) -> dict:
        """key -> commitment for the vk-side (non-proof) commitments."""
        out = {}
        for j, c in enumerate(self.table_commits):
            out[("tab", j)] = c
        for j, c in enumerate(self.selector_commits):
            out[("q", j)] = c
        for j, c in enumerate(self.fixed_commits):
            out[("fix", j)] = c
        for j, c in enumerate(self.sigma_commits):
            out[("sig", j)] = c
        for j, c in enumerate(self.sha_selector_commits or []):
            out[("shq", j)] = c
        if self.sha_k_commit is not None or self.config.num_sha_slots:
            out[("shk", 0)] = self.sha_k_commit
        return out

    def query_plan(self):
        """Ordered (key, rotation) pairs — the eval section of the proof."""
        cfg = self.config
        plan = []
        for j in range(cfg.num_advice):
            for rot in (0, 1, 2, 3):
                plan.append((("adv", j), rot))
        for j in range(cfg.num_lookup_advice):
            plan.append((("ladv", j), 0))
            plan.append((("pA", j), 0))
            plan.append((("pA", j), -1))
            plan.append((("pT", j), 0))
            plan.append((("lz", j), 0))
            plan.append((("lz", j), 1))
        for c in range(cfg.num_perm_chunks):
            plan.append((("pz", c), 0))
            plan.append((("pz", c), 1))
            if c + 1 < cfg.num_perm_chunks:
                plan.append((("pz", c), ROT_LAST))
        for j in range(cfg.num_advice):
            plan.append((("q", j), 0))
        for j in range(cfg.num_fixed):
            plan.append((("fix", j), 0))
        for j in range(cfg.num_perm_columns):
            plan.append((("sig", j), 0))
        for j in range(cfg.num_lookup_advice):
            plan.append((("tab", j), 0))
        if cfg.num_sha_slots:
            from .constraint_system import (SHA_A, SHA_ACT_WORD, SHA_CARRY,
                                            SHA_E, SHA_OUT_ROW, SHA_SEED_ROW,
                                            SHA_W)
            for i in range(32):                       # w bits
                for rot in (0, -2, -7, -15, -16):
                    plan.append((("shb", SHA_W + i), rot))
            for i in range(32):                       # a bits
                for rot in (0, -1, -2, -3, -4):
                    plan.append((("shb", SHA_A + i), rot))
            for i in range(32):                       # e bits
                for rot in (0, -1, -2, -3, -4):
                    plan.append((("shb", SHA_E + i), rot))
            for i in range(8):                        # carries
                plan.append((("shb", SHA_CARRY + i), 0))
            back = SHA_SEED_ROW - SHA_OUT_ROW
            for j in range(8):
                plan.append((("shw", j), 0))
                plan.append((("shw", j), back))
            plan.append((("shw", 8), 0))
            plan.append((("shw", SHA_ACT_WORD), 0))   # act flag
            plan.append((("shw", SHA_ACT_WORD), -1))
            from .constraint_system import SHA_NUM_SELECTORS
            for s in range(SHA_NUM_SELECTORS):
                plan.append((("shq", s), 0))
            plan.append((("shk", 0), 0))
        for i in range(NUM_H_CHUNKS):
            plan.append((("h", i), 0))
        return plan

    def assert_rotation_injective(self):
        """Distinct rotation TAGS in the query plan must evaluate to
        distinct points omega^rot · x: the in-circuit verifier and the EVM
        codegen key the SHPLONK sets by tag, while the native verifier
        dedupes by value — a collision (e.g. last_row ≡ a negative region
        rotation mod n) silently desynchronizes them."""
        dom = self.domain
        seen = {}
        for _key, rot in self.query_plan():
            idx = self.config.last_row if rot == ROT_LAST else rot % dom.n
            w = pow(dom.omega, idx, R)
            prev = seen.setdefault(w, rot)
            assert prev == rot or (isinstance(prev, int) and isinstance(rot, int)
                                   and prev % dom.n == rot % dom.n), \
                f"rotation tags {prev} and {rot} share omega^rot (zk_rows " \
                f"collision — adjust CircuitConfig.zk_rows)"

    def rotation_point(self, x: int, rot) -> int:
        dom = self.domain
        if rot == ROT_LAST:
            return pow(dom.omega, self.config.last_row, R) * x % R
        if rot < 0:
            return pow(dom.omega_inv, -rot, R) * x % R
        return pow(dom.omega, rot, R) * x % R


@dataclass
class ProvingKey:
    vk: VerifyingKey
    selector_polys: list      # coefficient form [n,4] arrays
    fixed_polys: list
    sigma_polys: list
    table_polys: list         # one per lookup-advice column
    # lagrange (value) forms kept for prover-side products
    selector_values: list
    fixed_values: list
    sigma_values: list        # int lists
    table_values: list        # one list per lookup-advice column
    sha_selector_polys: list = None
    sha_k_poly: object = None

    def column_poly(self, key):
        """Coefficient form of one of the key's own columns (a `KEY_KINDS`
        key)."""
        kind, j = key
        if kind == "shk":
            return self.sha_k_poly
        return {"q": self.selector_polys, "fix": self.fixed_polys,
                "sig": self.sigma_polys, "tab": self.table_polys,
                "shq": self.sha_selector_polys}[kind][j]

    def release_ext_cache(self):
        """Drop the prover's cached extended-domain forms of the key's
        columns: the host's (populated lazily by `_quotient_host`, ~GBs at
        k=21) and the device's (`quotient_device._key_ext`, filled on the
        key's first device prove). A service holding several pks calls this
        on the idle families so peaks don't stack
        (`prover_service/state.py`); with them its value columns' limb
        arrays (`prover._key_limbs`)."""
        from .prover import release_key_columns
        from .quotient_device import release_key_ext

        self.__dict__.pop("_ext_fixed_cache", None)
        release_key_columns(self)
        release_key_ext(self)


def keygen(srs: SRS, cfg: CircuitConfig, fixed_columns: list, selectors: list,
           copies: list, bk=None) -> ProvingKey:
    """Generate pk/vk from the circuit's fixed content.

    fixed_columns: [num_fixed][n] ints; selectors: [num_advice][n] 0/1;
    copies: global copy-constraint pairs."""
    bk = bk or B.get_backend()
    cfg.validate()
    dom = Domain(cfg.k)
    assert srs.n >= cfg.n, "SRS too small for circuit"

    sel_vals = [list(map(int, s)) for s in selectors]
    fix_vals = [list(map(int, f)) for f in fixed_columns]
    # one table build per DISTINCT table id; columns share the objects
    tab_by_id = {tid: table_column(cfg, tid)
                 for tid in {cfg.table_id(j) for j in range(cfg.num_lookup_advice)}}
    tab_vals = [tab_by_id[cfg.table_id(j)] for j in range(cfg.num_lookup_advice)]
    sigma_vals = build_sigma(cfg, copies)

    def to_poly(vals):
        return dom.lagrange_to_coeff(B.to_arr(vals), bk)

    sel_polys = [to_poly(v) for v in sel_vals]
    fix_polys = [to_poly(v) for v in fix_vals]
    sig_polys = [to_poly(v) for v in sigma_vals]
    tab_poly_by_id = {tid: to_poly(v) for tid, v in tab_by_id.items()}
    tab_polys = [tab_poly_by_id[cfg.table_id(j)]
                 for j in range(cfg.num_lookup_advice)]
    tab_ids = sorted(tab_poly_by_id)

    sha_sel_polys, sha_k_poly = None, None
    sha_sel_commits, sha_k_commit = None, None
    if cfg.num_sha_slots:
        from .constraint_system import sha_selector_columns
        sha_sel, sha_k = sha_selector_columns(cfg)
        sha_sel_polys = [to_poly(v) for v in sha_sel]
        sha_k_poly = to_poly(sha_k)

    # all vk commitments in ONE batched backend call (device base cached,
    # batch axis mesh-shardable — same machinery as the prover commit phase)
    batch = (sel_polys + fix_polys + sig_polys
             + [tab_poly_by_id[t] for t in tab_ids]
             + (sha_sel_polys or [])
             + ([sha_k_poly] if sha_k_poly is not None else []))
    pts = kzg.commit_many(srs, batch, bk)
    off = 0
    def take(k):
        nonlocal off
        out = pts[off:off + k]
        off += k
        return out
    sel_commits = take(len(sel_polys))
    fix_commits = take(len(fix_polys))
    sig_commits = take(len(sig_polys))
    tab_commit_by_id = dict(zip(tab_ids, take(len(tab_ids))))
    if cfg.num_sha_slots:
        sha_sel_commits = take(len(sha_sel_polys))
        sha_k_commit = take(1)[0]

    vk = VerifyingKey(
        config=cfg,
        selector_commits=sel_commits,
        fixed_commits=fix_commits,
        sigma_commits=sig_commits,
        table_commits=[tab_commit_by_id[cfg.table_id(j)]
                       for j in range(cfg.num_lookup_advice)],
        sha_selector_commits=sha_sel_commits,
        sha_k_commit=sha_k_commit,
    )
    vk.assert_rotation_injective()
    return ProvingKey(vk, sel_polys, fix_polys, sig_polys, tab_polys,
                      sel_vals, fix_vals, sigma_vals, tab_vals,
                      sha_selector_polys=sha_sel_polys,
                      sha_k_poly=sha_k_poly)
