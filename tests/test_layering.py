"""Which way the package's imports point.

The sub-packages are ranked `kernels, runtime < ops, core < parallel <
search < compiler < serving`: a module imports from its own rank and below.
Inside `serving/` the data plane (what a serving cell runs) does not import
the control plane (`fleet`, `twin`). Every `import` statement counts, those
inside functions too: that is where a cycle hides, and where an import of a
module that is gone hides behind `except ImportError`, so the module named
has to exist. The top-level modules (`telemetry`, `health`, `attribution`,
`config`, ..) and the frontends are not ranked.

The imports that point up today stand in `STANDING`, each beside the
ROADMAP debt that retires it. A new one fails here: move what both sides
need DOWN (as PR 60 moved the page format into `ops/pages.py`) instead of
reaching up from inside a function. This file reads source with `ast` and
imports nothing of the package or of jax.
"""

import ast
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "flexflow_tpu"
RANK = {"kernels": 0, "runtime": 0, "ops": 1, "core": 1, "parallel": 2,
        "search": 3, "compiler": 4, "serving": 5}
DATA_PLANE = ("admission", "engine", "kv_cache", "program", "scheduler")
CONTROL_PLANE = ("fleet", "twin")

# (module, the higher package it imports): the ROADMAP debt that retires it
STANDING = {
    ("core/model.py", "compiler"):
        "D12: FFModel.compile, the frontends' entry, calls the compiler",
    ("core/model.py", "search"):
        "D12: FFModel.compile's strategy import / export",
    ("compiler/compile.py", "serving"):
        "D10: the compile_serving shim beside compile_model",
    ("parallel/pipeline.py", "search"):
        "D6: the pipelined fit loop searches its own cut points",
    ("parallel/pipeline.py", "compiler"):
        "D6: two fit loops; one loop with one stage as the degenerate case",
    ("parallel/default_strategy.py", "search"):
        "D6: the data-parallel default reads search.candidates' layouts",
    ("kernels/partition.py", "parallel"):
        "D28: per_shard reads the mesh helpers of parallel/",
    ("ops/fork_join.py", "parallel"):
        "D28: a placed fork-join lowers through parallel.interop",
}


def _modules():
    out = []
    for pkg in RANK:
        top = os.path.join(ROOT, PACKAGE, pkg)
        for d, _, files in os.walk(top):
            out += [os.path.relpath(os.path.join(d, f),
                                    os.path.join(ROOT, PACKAGE))
                    for f in files if f.endswith(".py")]
    return sorted(p.replace(os.sep, "/") for p in out)


def _is_module(parts):
    path = os.path.join(ROOT, PACKAGE, *parts)
    return os.path.isfile(path + ".py") or os.path.isdir(path)


def _imported(rel):
    """`(line, dotted name under the package, the module's part of it)` of
    every import statement of the package's own modules in `rel`."""
    with open(os.path.join(ROOT, PACKAGE, rel)) as f:
        tree = ast.parse(f.read())
    here = [PACKAGE] + rel.split("/")[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names, attr = [a.name for a in node.names], 0
        elif isinstance(node, ast.ImportFrom):
            base = ".".join(here[:len(here) - node.level + 1]) \
                if node.level else ""
            mod = ".".join(x for x in (base, node.module or "") if x)
            names, attr = [f"{mod}.{a.name}" for a in node.names], 1
        else:
            continue
        for name in names:
            if name == PACKAGE or name.startswith(PACKAGE + "."):
                parts = name.split(".")[1:]
                yield node.lineno, parts, parts[:len(parts) - attr]


@pytest.mark.parametrize("rel", _modules())
def test_a_module_imports_its_own_rank_and_below(rel):
    pkg = rel.split("/")[0]
    up, reaches = [], set()
    for line, name, module in _imported(rel):
        if not _is_module(module):
            up.append(f"{rel}:{line} imports {'.'.join(name)}: no module "
                      f"{'.'.join([PACKAGE] + module)}")
        if not name:
            continue
        if name[0] in RANK and RANK[name[0]] > RANK[pkg]:
            reaches.add(name[0])
            if (rel, name[0]) not in STANDING:
                up.append(f"{rel}:{line} imports {'.'.join(name)}: "
                          f"{pkg} ranks under {name[0]}")
        if pkg == name[0] == "serving" and len(name) > 1 \
                and rel.split("/")[1][:-3] in DATA_PLANE \
                and name[1] in CONTROL_PLANE:
            up.append(f"{rel}:{line} imports serving.{name[1]}: the data "
                      "plane does not import the control plane")
    assert not up, "\n".join(up)
    stale = [k for k in STANDING if k[0] == rel and k[1] not in reaches]
    assert not stale, f"retired: take {stale} out of STANDING"


def test_every_standing_exception_names_a_module():
    mods = set(_modules())
    assert all(rel in mods and pkg in RANK for rel, pkg in STANDING)
