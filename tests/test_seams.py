"""Seams between files that only a reader used to hold together (PR 33).

A counter the chip smoke and the benchmark hold at zero is a name in three
places: the two lists and the `incr` that ticks it. A variable the README
documents is a name in two: the README and the `os.environ` read. A Makefile
recipe names files. Each of these goes stale without a failing test when one
side is renamed or deleted, and a zero-check on a counter nothing ticks, or
a documented switch nothing reads, looks like a guarantee. Nothing here
imports JAX or runs the prover: the files are read.
"""

import ast
import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(*parts) -> str:
    with open(os.path.join(REPO, *parts), encoding="utf-8") as fh:
        return fh.read()


def _module_tuple(path: str, name: str) -> tuple:
    """The literal tuple a module assigns to `name` at top level (read, not
    imported: chip_smoke.py and perfbench/ are programs, not libraries)."""
    for node in ast.parse(_read(path)).body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == name
                for t in node.targets):
            return tuple(ast.literal_eval(node.value))
    raise AssertionError(f"{path} assigns no {name}")


@functools.lru_cache(maxsize=None)
def _py_files() -> tuple:
    """The checkout's .py files, less hidden directories (caches, scratch
    copies) and what the chip tool brings back."""
    found = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        found += [os.path.relpath(os.path.join(root, f), REPO)
                  for f in files if f.endswith(".py")]
    return tuple(found)


@functools.lru_cache(maxsize=None)
def _incr_patterns() -> tuple:
    """One regex a `<health>.incr(<name>)` call under spectre_tpu/: the
    literal name, or for an f-string its text with `.+` where a value is
    formatted in (`incr(f"prove_cpu_fallbacks_{kind}")`)."""
    patterns = []
    for rel in _py_files():
        if not rel.startswith("spectre_tpu/"):
            continue
        for node in ast.walk(ast.parse(_read(rel))):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "incr" and node.args):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                patterns.append(re.escape(arg.value))
            elif isinstance(arg, ast.JoinedStr):
                patterns.append("".join(
                    re.escape(v.value) if isinstance(v, ast.Constant)
                    else ".+" for v in arg.values))
    return tuple(patterns)


SMOKE_ZERO_COUNTERS = _module_tuple("chip_smoke.py", "ZERO_COUNTERS")


class TestZeroCounters:
    @pytest.mark.parametrize("name", SMOKE_ZERO_COUNTERS)
    def test_zero_counter_can_tick(self, name):
        """Every counter `chip_smoke.py` holds at zero has an `incr` site
        under spectre_tpu/ that can name it: a renamed or deleted counter
        would otherwise leave the smoke's and the benchmark's zero-check
        passing on a name nothing ticks. (At the parent no test tied the
        lists to the sites.)"""
        assert any(re.fullmatch(p, name) for p in _incr_patterns()), \
            f"no incr() under spectre_tpu/ can tick {name!r}"

    def test_benchmark_holds_at_zero_what_the_smoke_does(self):
        """The benchmark's `correct` reads at least the counters the smoke
        reads (perfbench/servers/single.py, which this PR may not edit and
        does not): a counter added to the smoke alone would be held at zero
        on a hand run and on no measured one."""
        bench = _module_tuple("perfbench/servers/single.py", "ZERO_COUNTERS")
        assert set(SMOKE_ZERO_COUNTERS) <= set(bench), \
            sorted(set(SMOKE_ZERO_COUNTERS) - set(bench))


class TestDocumentsNameWhatExists:
    def test_readme_names_no_variable_nothing_reads(self):
        """Every SPECTRE_* name in README.md appears in some .py file of
        the tree (a trailing `_` is a family: some name begins with it).
        Deleting a switch and leaving its paragraph fails here."""
        names = set(re.findall(r"SPECTRE_[A-Z0-9_]*[A-Z0-9]_?",
                               _read("README.md")))
        source = "\n".join(_read(rel) for rel in _py_files()
                           if rel != "tests/test_seams.py")
        read = set(re.findall(r"SPECTRE_[A-Z0-9_]+", source))
        unread = sorted(
            n for n in names
            if not (any(r.startswith(n) for r in read) if n.endswith("_")
                    else n in read))
        assert not unread, f"README.md documents {unread}: nothing reads them"

    def test_makefile_recipes_name_files_that_exist(self):
        """Every `python <file>.py`, every `compileall` argument and every
        tests/*.py a Makefile recipe names is a path of the checkout."""
        named = set()
        for line in _read("Makefile").splitlines():
            if not line.startswith("\t"):
                continue
            words = line.split()
            named.update(w for w in words
                         if w.endswith(".py") and not w.startswith("-"))
            if "compileall" in words:
                named.update(w for w in words[words.index("compileall") + 1:]
                             if not w.startswith("-"))
        assert named, "the Makefile's recipes name no file at all"
        missing = sorted(p for p in named
                         if not os.path.exists(os.path.join(REPO, p)))
        assert not missing, f"Makefile recipes name {missing}"
