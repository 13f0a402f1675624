"""The arrows between the packages of ``yask_tpu/`` point down.

Every module of a package is parsed with ``ast`` (function-local
imports included) and each ``yask_tpu.*`` import must go to a package
at or below the importer's in ``ORDER``; none may import the
repo-root ``tools``, ``benchmark``, ``bench`` or ``chip_smoke`` — the
program does not measure through what measures it.  An upward import
that exists is a named exception with its ROADMAP debt, never allowed
silently; a new one fails here.
"""

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(ROOT, "yask_tpu")

#: bottom → top.  ``__init__`` is the facade ``yask_tpu/__init__.py``
#: (``from yask_tpu import yk_factory``); ``main`` is ``main.py``.
ORDER = ["native", "utils", "backend", "compiler", "stencils", "obs",
         "resilience", "cache", "ops", "parallel", "runtime",
         "__init__", "serve", "checker", "main", "tools"]
RANK = {name: i for i, name in enumerate(ORDER)}

#: never imported from inside the program
OUTSIDE = {"tools", "benchmark", "bench", "chip_smoke"}

#: (importing file under yask_tpu/, imported package) → why it stands.
#: All are ROADMAP D13's; none was added by the PR that wrote this file.
EXCEPTIONS = {
    ("compiler/solution_base.py", "stencils"):
        "D13: the registry imports the library to run its "
        "@register_solution decorators",
    ("compiler/solution.py", "__init__"):
        "D13: __version__ read from the facade",
    ("runtime/factory.py", "__init__"):
        "D13: __version__ read from the facade",
    ("ops/pipeline.py", "checker"):
        "D13: the pipeline plans its fused tile with checker.vmem."
        "plan_pallas",
    ("ops/pipeline.py", "runtime"):
        "D13: SolutionPipeline builds StencilContexts",
    ("runtime/auto_tuner.py", "checker"):
        "D13: the tuner keys its VMEM ladder on checker.vmem.plan_pallas",
    ("serve/server.py", "checker"):
        "D13: open_session runs the checker's serve pass, log-only",
}


def _modules(case, pkg):
    if case in ("__init__", "main"):
        return [os.path.join(pkg, case + ".py")]
    out = []
    for dp, dn, fn in os.walk(os.path.join(pkg, case)):
        dn[:] = [d for d in dn if d != "__pycache__"]
        out += [os.path.join(dp, f) for f in fn if f.endswith(".py")]
    return sorted(out)


def _imports(path, pkg):
    """(lineno, dotted module) of every import in ``path``, relative
    ones resolved, ``from yask_tpu import x`` as ``yask_tpu.x``."""
    rel = os.path.relpath(path, os.path.dirname(pkg))[:-3].split(os.sep)
    here = rel[:-1]            # the package a relative import starts at
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield node.lineno, a.name
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if node.level:
                base = here[:len(here) - (node.level - 1)]
                mod = ".".join(base + ([mod] if mod else []))
            if mod == "yask_tpu":
                for a in node.names:
                    yield node.lineno, "yask_tpu." + a.name
            else:
                yield node.lineno, mod


def _target(mod):
    """The ORDER entry (or OUTSIDE name) an import lands in, else None."""
    parts = mod.split(".")
    if parts[0] in OUTSIDE:
        return parts[0]
    if parts[0] != "yask_tpu":
        return None
    if len(parts) == 1:
        return "__init__"
    return parts[1] if parts[1] in RANK else "__init__"


def violations(case, pkg=PKG):
    bad, used = [], set()
    for path in _modules(case, pkg):
        rel = os.path.relpath(path, pkg)
        for lineno, mod in _imports(path, pkg):
            tgt = _target(mod)
            if tgt is None or tgt == case:
                continue
            if tgt in OUTSIDE:
                bad.append(f"{rel}:{lineno} imports {mod} "
                           "(outside the program)")
            elif RANK[tgt] > RANK[case]:
                if (rel, tgt) in EXCEPTIONS:
                    used.add((rel, tgt))
                else:
                    bad.append(f"{rel}:{lineno} imports {mod}: "
                               f"'{tgt}' is above '{case}'")
    return bad, used


def test_order_names_every_package():
    on_disk = {d for d in os.listdir(PKG)
               if os.path.isfile(os.path.join(PKG, d, "__init__.py"))}
    on_disk |= {f[:-3] for f in os.listdir(PKG) if f.endswith(".py")}
    assert on_disk == set(ORDER)


@pytest.mark.parametrize("case", ORDER)
def test_imports_point_down(case):
    bad, used = violations(case)
    assert not bad, "\n".join(bad)
    # an exception that no longer exists must leave the list
    stale = {k for k in EXCEPTIONS
             if k[0].split("/")[0].replace(".py", "") == case} - used
    assert not stale, f"exceptions no import needs any more: {stale}"


def test_every_exception_names_a_roadmap_debt():
    with open(os.path.join(ROOT, "ROADMAP.md")) as f:
        roadmap = f.read()
    for why in EXCEPTIONS.values():
        item = why.split(":")[0]
        assert f"**{item}." in roadmap, item


def test_rule_catches_an_upward_and_an_outside_import(tmp_path):
    src = tmp_path / "yask_tpu" / "parallel"
    src.mkdir(parents=True)
    (src / "x.py").write_text(
        "def f():\n"
        "    from yask_tpu.runtime.stats import yk_stats\n"
        "    import benchmark.run\n"
        "from yask_tpu.utils import cli\n"
        "from . import mesh\n")
    bad, _ = violations("parallel", pkg=str(tmp_path / "yask_tpu"))
    assert len(bad) == 2
    assert "'runtime' is above 'parallel'" in bad[0]
    assert "outside the program" in bad[1]
