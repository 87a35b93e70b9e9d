"""Bring-up contracts (PR 25): the program changes that let the served prove
run on a chip — and nothing that lets it finish on the CPU unnoticed.

* `setup_compile_cache` places the persistent compile cache from outside
  (`JAX_COMPILATION_CACHE_DIR`) or at ONE fixed path inside the checkout;
* `cli --backend tpu` refuses a JAX that is not running on a TPU;
* `ProverState` builds a circuit's proving key on first use, not at boot;
* `chip_smoke.py` without a TPU exits non-zero and never prints `"ok": true`.
"""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from spectre_tpu import spec as SP
from spectre_tpu.plonk import backend as B

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCachePlacement:
    @pytest.fixture(autouse=True)
    def _unset_cache_dir(self):
        """conftest already placed the cache; start each case from a clean
        config and put the session's value back afterwards."""
        old = jax.config.jax_compilation_cache_dir
        jax.config.update("jax_compilation_cache_dir", None)
        yield
        jax.config.update("jax_compilation_cache_dir", old)

    def test_env_var_means_no_directory_set_in_code(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        B.setup_compile_cache()
        # JAX reads the variable itself (at import); the program set nothing
        assert jax.config.jax_compilation_cache_dir is None

    def test_fixed_directory_inside_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        B.setup_compile_cache()
        first = jax.config.jax_compilation_cache_dir
        assert first == os.path.join(
            REPO, ".jax_cache", f"cpu_{B._host_fingerprint()}")
        jax.config.update("jax_compilation_cache_dir", None)
        B.setup_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first  # never moves

    def test_an_already_placed_cache_is_left_alone(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        jax.config.update("jax_compilation_cache_dir", "/placed/earlier")
        B.setup_compile_cache()
        assert jax.config.jax_compilation_cache_dir == "/placed/earlier"

    def test_cache_dir_is_git_ignored(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


class TestCliRefusesCpuOnlyJax:
    @pytest.mark.parametrize("argv", [
        ["--backend", "tpu", "rpc", "--port", "0"],
        ["--backend", "tpu", "--spec", "tiny", "circuit", "committee-update",
         "setup", "--k", "10"],
    ])
    def test_backend_tpu_exits_naming_the_platform(self, argv, monkeypatch):
        from spectre_tpu.prover_service import cli, state
        monkeypatch.setattr(
            state, "ProverState",
            lambda *a, **k: pytest.fail("state built without a TPU"))
        with pytest.raises(SystemExit) as exc:
            cli.main(argv)
        msg = str(exc.value)
        assert exc.value.code not in (0, None)
        assert "'cpu'" in msg and "not a TPU" in msg

    def test_get_backend_tpu_still_runs_on_the_cpu(self):
        """The class is reachable on XLA:CPU (the tests' TpuBackend tier);
        only the operator's entry point asks what JAX is running on."""
        assert B.get_backend("tpu").name == "tpu"
        assert jax.devices()[0].platform == "cpu"


class _FakePk:
    def __init__(self, name):
        self.name = name
        self.released = 0

    def release_ext_cache(self):
        self.released += 1


class TestLazyProvingKeys:
    @pytest.fixture
    def state(self, monkeypatch, tmp_path):
        from spectre_tpu.prover_service import selfverify, state as st
        calls = []

        def fake_create_pk(name):
            def create_pk(srs, spec, k, dummy_args, bk=None, cache=True):
                calls.append(name)
                return _FakePk(name)
            return create_pk

        monkeypatch.setattr(st.StepCircuit, "create_pk",
                            fake_create_pk("step"))
        monkeypatch.setattr(st.CommitteeUpdateCircuit, "create_pk",
                            fake_create_pk("committee"))
        monkeypatch.setattr(st.SRS, "load_or_setup",
                            classmethod(lambda cls, k, d=None: ("srs", k)))
        monkeypatch.setattr(selfverify.SelfCheck, "run", lambda self: True)
        s = st.ProverState(SP.TINY, 10, 10, params_dir=str(tmp_path))
        s.keygen_calls = calls
        return s

    def test_no_keygen_at_construction(self, state):
        assert state.keygen_calls == []
        assert state._pks == {}

    def test_first_reader_builds_only_its_circuit_once(self, state):
        pk = state.committee_pk
        assert state.keygen_calls == ["committee"]
        assert state.committee_pk is pk
        assert state.keygen_calls == ["committee"]       # cached
        assert state.step_pk.name == "step"
        assert state.keygen_calls == ["committee", "step"]

    def test_prove_committee_keygens_committee_only(self, state, monkeypatch):
        from spectre_tpu.prover_service import state as st
        monkeypatch.setattr(
            st.CommitteeUpdateCircuit, "prove",
            classmethod(lambda cls, pk, srs, args, spec, bk: b"\x01" * 8))
        monkeypatch.setattr(
            st.CommitteeUpdateCircuit, "get_instances",
            classmethod(lambda cls, args, spec: [7]))
        proof, inst = state.prove_committee(object())
        assert (proof, inst) == (b"\x01" * 8, [7])
        assert state.keygen_calls == ["committee"]
        assert "step" not in state._pks

    def test_idle_cache_release_never_triggers_keygen(self, state):
        state._release_idle_ext_caches()                  # nothing built
        assert state.keygen_calls == []
        committee, step = state.committee_pk, state.step_pk
        state._release_idle_ext_caches(committee)
        assert (committee.released, step.released) == (0, 1)


class TestChipSmokeWithoutATpu:
    def _run(self, cwd, script):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                              capture_output=True, text=True, timeout=300)

    def test_exits_nonzero_and_prints_no_ok(self):
        r = self._run(REPO, os.path.join(REPO, "chip_smoke.py"))
        assert r.returncode != 0
        assert '"ok": true' not in r.stdout
        assert "platform 'cpu'" in r.stderr and "not a TPU" in r.stderr

    def test_alone_in_a_directory_it_fails_too(self, tmp_path):
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        r = self._run(str(tmp_path), str(tmp_path / "chip_smoke.py"))
        assert r.returncode != 0
        assert '"ok": true' not in r.stdout

    def test_platform_is_checked_before_anything_is_built(self, monkeypatch):
        """No TPU: nothing of the program is touched — no native rebuild,
        no work directory."""
        sys.path.insert(0, REPO)
        try:
            import chip_smoke
        finally:
            sys.path.remove(REPO)
        monkeypatch.setattr(chip_smoke, "prepare",
                            lambda *a: pytest.fail("prepare() ran"))
        assert chip_smoke.main([]) == 1
        assert not os.path.exists(chip_smoke.WORK)
