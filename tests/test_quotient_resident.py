"""The device quotient keeps the proving key's extended columns on the
device (`plonk/quotient_device.py`: `_key_ext`, the synthetic rows beside
`xcol`): a key's first prove on `TpuBackend` extends every column and keeps
the key's; the next reads them. Proofs are the bytes `CpuBackend` makes,
whatever the store holds.

Every prove here is the tiny circuit at `_shapes.TINY_K` on the one-chip
plan (XLA:CPU), the programs `tests/test_device_prove.py` compiles: no new
kernel shape. The mesh engine's own store is
`tests/test_quotient_sharded.py::test_mesh_engine_keeps_its_own_store`.
"""

import copy
import math
import sys
import threading
import types

import numpy as np
import pytest

from spectre_tpu.observability import compilelog, tracing
from spectre_tpu.plonk import backend as B, quotient_device as QD
from spectre_tpu.plonk.expressions import referenced_keys
from spectre_tpu.plonk.keygen import KEY_KINDS, ext_cache_budget_bytes
from spectre_tpu.plonk.prover import prove
from spectre_tpu.plonk.verifier import verify

from _shapes import TINY_SEED, seeded_blinding


def _walk(s):
    yield s
    for c in s.children:
        yield from _walk(c)


def _fresh_key(tiny):
    """A key no prove has read: the tiny key's columns under another
    identity, its circuit's synthetic rows dropped too."""
    pk = copy.copy(tiny.pk)
    pk.release_ext_cache()
    return pk


def _shape(tiny):
    """(column keys, the key's own among them, an LDE chunk, bytes of one
    extended column)."""
    m = tiny.pk.vk.domain.n_ext
    plan = referenced_keys(tiny.cfg)
    own = [k for k in plan if k[0] in KEY_KINDS]
    return plan, own, QD._ext_chunk(m), m * 16 * 4


def _prove(tiny, pk):
    """One seeded prove on `TpuBackend` under a trace: (proof, the
    `quotient/extend` span's meta, its dispatch count)."""
    with tracing.trace("resident") as tr:
        proof = prove(pk, tiny.srs, tiny.asg, B.get_backend("tpu"),
                      blinding_rng=seeded_blinding(TINY_SEED))
    (ext,) = [s for s in _walk(tr.root) if s.name == "quotient/extend"]
    dispatches = tracing.summary(tr)["span_counts"]["quotient/extend/dispatch"]
    return proof, ext.meta, dispatches


def _held(pk) -> dict:
    """The key's single-device store, read back to the host."""
    entry = QD._key_ext.get(id(pk))
    store = entry[1].get(QD._LocalEngine.placement, {}) if entry else {}
    return {k: np.asarray(a) for k, a in store.items()}


@pytest.fixture(scope="module")
def served(tiny):
    """A fresh key proved three times on the one-chip plan: the fill (a
    warm-up), a served prove (its compiles captured), and after
    `release_ext_cache` the refill."""
    compilelog.install()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("SPECTRE_MESH_SHAPE", "1x1")
        mp.delenv("SPECTRE_EXT_CACHE_MB", raising=False)
        pk = _fresh_key(tiny)
        fill = _prove(tiny, pk)
        held = _held(pk)
        with compilelog.capture() as events:
            read = _prove(tiny, pk)
        pk.release_ext_cache()
        released = _held(pk)
        refill = _prove(tiny, pk)
        refilled = _held(pk)
        pk.release_ext_cache()
    return types.SimpleNamespace(
        pk=pk, fill=fill, read=read, refill=refill, held=held,
        released=released, refilled=refilled,
        compiles=compilelog.summarize(events)["count"])


class TestResidentKeyColumns:

    def test_every_prove_is_the_cpu_backend_s_bytes(self, served, tiny,
                                                    tiny_cpu_proof):
        for proof, _, _ in (served.fill, served.read, served.refill):
            assert proof == tiny_cpu_proof
        assert verify(served.pk.vk, tiny.srs, tiny.instances,
                      served.read[0])

    def test_the_next_prove_reads_the_key_s_columns(self, served, tiny):
        plan, own, chunk, col = _shape(tiny)
        _, fill, fill_dispatches = served.fill
        _, read, read_dispatches = served.read
        # the fill: every column and the three synthetic rows extended,
        # the synthetic rows as one batch, the rest in chunks
        assert (fill["resident"], fill["extended"]) == (0, len(plan) + 3)
        assert fill_dispatches == 1 + math.ceil(len(plan) / chunk)
        # the next: the key's columns and the synthetic rows read, the
        # prove's own extended; the chunks the key's columns filled go
        assert (read["resident"], read["extended"]) \
            == (len(own) + 3, len(plan) - len(own))
        assert read_dispatches == math.ceil((len(plan) - len(own)) / chunk)
        assert read_dispatches < fill_dispatches
        for meta in (fill, read):
            assert meta["resident_bytes"] == (len(own) + 3) * col
        assert sorted(served.held) == sorted(own)

    def test_a_served_prove_compiles_nothing(self, served):
        assert served.compiles == 0

    def test_release_empties_the_store_and_the_refill_is_the_same(
            self, served):
        assert served.released == {}
        _, refill, _ = served.refill
        assert refill["resident"] == 0
        assert sorted(served.refilled) == sorted(served.held)
        for k, arr in served.held.items():
            assert np.array_equal(served.refilled[k], arr), k


@pytest.mark.parametrize("fit", [0, 6])
def test_a_budget_keeps_what_fits(fit, tiny, tiny_cpu_proof, monkeypatch):
    """SPECTRE_EXT_CACHE_MB holds `fit` extended columns: the synthetic
    rows first, then the key's (none where fewer than the three rows fit).
    The rest is extended on every prove, and the bytes do not change."""
    plan, own, chunk, col = _shape(tiny)
    monkeypatch.setenv("SPECTRE_MESH_SHAPE", "1x1")
    monkeypatch.setenv("SPECTRE_EXT_CACHE_MB",
                       str((fit * col + col // 2) / (1 << 20)))
    kept = fit if fit >= 3 else 0
    own_kept = max(0, kept - 3)
    assert own_kept < len(own)          # the key does not fit whole
    pk = _fresh_key(tiny)
    try:
        for resident in (0, kept):
            proof, meta, dispatches = _prove(tiny, pk)
            assert proof == tiny_cpu_proof
            assert meta["resident"] == resident
            assert meta["extended"] == len(plan) + 3 - resident
            assert meta["resident_bytes"] == kept * col
        assert len(_held(pk)) == own_kept
        # the synthetic rows' batch where they are not kept, then chunks
        assert dispatches == (kept == 0) + math.ceil(
            (len(plan) - own_kept) / chunk)
    finally:
        pk.release_ext_cache()


def test_two_jobs_proving_one_key_fill_it_once(tiny, tiny_cpu_proof,
                                               monkeypatch):
    """Both jobs reach the store together (a barrier in front of its
    lock); one extends the key's columns, the other reads them."""
    plan, own, _, _ = _shape(tiny)
    monkeypatch.setenv("SPECTRE_MESH_SHAPE", "1x1")
    monkeypatch.delenv("SPECTRE_EXT_CACHE_MB", raising=False)
    pk = _fresh_key(tiny)
    together = threading.Barrier(2, timeout=120)
    budget = QD._device_budget_bytes

    def at_once():
        together.wait()
        return budget()

    monkeypatch.setattr(QD, "_device_budget_bytes", at_once)
    results, errors = [None, None], []

    def job(i):
        try:
            results[i] = _prove(tiny, pk)
        except Exception as e:       # read below: a failed job fails here
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=job, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        pk.release_ext_cache()
    assert not errors, errors
    for proof, _, _ in results:
        assert proof == tiny_cpu_proof
        assert verify(pk.vk, tiny.srs, tiny.instances, proof)
    reads = sorted((meta["resident"], meta["extended"])
                   for _, meta, _ in results)
    assert reads == [(0, len(plan) + 3),
                     (len(own) + 3, len(plan) - len(own))]


@pytest.mark.parametrize("env,memory,want", [
    ("1.5", 16 << 30, 3 << 19),     # the variable, where set, wins
    (None, 16 << 30, int((16 << 30) * 0.15)),   # 15 % of the device
])
def test_the_budget_reads_the_variable_then_the_memory(env, memory, want,
                                                       monkeypatch):
    if env is None:
        monkeypatch.delenv("SPECTRE_EXT_CACHE_MB", raising=False)
    else:
        monkeypatch.setenv("SPECTRE_EXT_CACHE_MB", env)
    assert ext_cache_budget_bytes(memory) == want


def test_a_device_with_no_limit_takes_the_host_s_rule(monkeypatch):
    """XLA:CPU reports no `bytes_limit`: the host store's rule, min(16 GB,
    15 % of RAM)."""
    monkeypatch.delenv("SPECTRE_EXT_CACHE_MB", raising=False)
    assert QD._device_budget_bytes() == ext_cache_budget_bytes()
    assert 0 < ext_cache_budget_bytes() <= 16 << 30
