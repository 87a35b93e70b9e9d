"""Work directory: one per configuration AND host fingerprint, inside the
checkout, git-ignored by the root .gitignore. The SRS, the native library's
build and the proving-key pickle are made by a cell's first run there and
loaded by the later ones; nothing built on another host is picked up (the
fingerprint is the one `setup_compile_cache` keys `.jax_cache/` by: CPU
model + feature flags + jaxlib version)."""

from __future__ import annotations

import hashlib
import os
import platform
import shutil
import subprocess

from .cells import BENCH_DIR, ROOT, BenchError


def host_fingerprint() -> str:
    feat = model = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                if not feat and line.startswith(("flags", "Features")):
                    feat = line.strip()
                if not model and line.startswith("model name"):
                    model = line.strip()
                if feat and model:
                    break
    except OSError:
        pass
    try:
        import jaxlib
        jl = getattr(jaxlib, "__version__", "")
    except ImportError:
        jl = ""
    ident = f"{platform.machine()}|{model}|{feat}|jaxlib-{jl}"
    return hashlib.blake2s(ident.encode(), digest_size=4).hexdigest()


def prepare(config: dict) -> dict:
    """Make the directories and set BUILD_DIR / PARAMS_DIR (read when
    spectre_tpu is first imported). Returns the paths."""
    work = os.path.join(BENCH_DIR, "work",
                        f"{config['name']}-{host_fingerprint()}")
    paths = {"work": work, "build": os.path.join(work, "build"),
             "params": os.path.join(work, "params"),
             "journal": os.path.join(work, "journal"),
             "trace": os.path.join(work, "trace")}
    # the journal dedups a witness onto a finished job: a run with a seed
    # this checkout has seen would prove nothing. Traces are per run too.
    for fresh in ("journal", "trace"):
        shutil.rmtree(paths[fresh], ignore_errors=True)
    for p in paths.values():
        os.makedirs(p, exist_ok=True)
    os.environ["BUILD_DIR"] = paths["build"]
    os.environ["PARAMS_DIR"] = paths["params"]
    pin = config.get("pinning")
    if pin:                       # a tracked pinning rides along; a pk never
        shutil.copy(os.path.join(ROOT, pin), paths["build"])
    native = os.path.join(ROOT, "spectre_tpu", "native")
    if not os.path.isdir(native):
        raise BenchError(f"{native} not found: the benchmark runs from the "
                         f"root of a spectre-tpu checkout")
    if not os.path.exists(os.path.join(native, "libspectre_host.so")):
        r = subprocess.run(["make", "-C", native], capture_output=True,
                           text=True)
        if r.returncode != 0:
            raise BenchError(f"native host library build failed:\n"
                             f"{r.stdout}{r.stderr}")
    return paths
