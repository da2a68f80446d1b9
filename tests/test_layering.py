"""The package graph of ``apex_tpu/``, held to one table.

A stdlib-``ast`` walk over every import of every module (function-level
ones too) finds which top-level siblings each top-level package or module
of ``apex_tpu/`` imports. A package imports only from its row below. A row
may shrink in a later PR; it never grows without its reason beside it.
"""

import ast
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "apex_tpu")

#: package -> the siblings it may import
ALLOWED = {
    "amp": {"ops", "optimizers"},       # amp -> optimizers -> transformer
                                        # -> amp: the library's old cycle
    "analysis": {"models", "ops", "serving"},   # the lint harness traces
                                                # the programs it checks
    "collectives": set(),
    "contrib": {"amp", "collectives", "fp16_utils", "mesh",
                "normalization", "ops", "optimizers", "parallel",
                "transformer"},
    "data": set(),
    "fp16_utils": {"amp", "optimizers"},
    "fused_dense": {"amp"},
    "mesh": set(),
    "mlp": {"amp"},
    "models": {"amp", "mesh", "normalization", "ops", "optimizers",
               "serving", "transformer"},
    "normalization": {"ops"},
    "obs": {"utils"},
    "ops": {"mesh"},
    "optimizers": {"mesh", "ops", "transformer"},
    "parallel": {"mesh", "ops", "optimizers", "transformer"},
    "serving": {"amp", "mesh", "models", "obs", "ops", "transformer",
                "utils"},
    "transformer": {"amp", "collectives", "mesh", "ops"},
    "utils": {"amp", "transformer"},
}

#: an edge that only the named files may hold
ONLY_FROM = {
    # the one upward edge: lock-step ``generate(paged=True)`` builds the
    # pool it decodes from (ROADMAP Design 3)
    ("models", "serving"): {"apex_tpu/models/generation.py"},
}

#: what a package may import of the repo's root (``benchmark/``, the root
#: scripts, ``tests/``): nothing, with one exception
ROOT_ALLOWED = {
    # the lint registry traces ``tpu_aot.kernel_cases()`` and the tp4
    # acceptance shapes, so that the AOT sweep and the lint tiers see the
    # same programs; imported inside functions, found through ``--root``
    "analysis": {"tpu_aot"},
}

ROOT_NAMES = {n[:-3] if n.endswith(".py") else n
              for n in os.listdir(REPO)
              if (n.endswith(".py") or os.path.isdir(os.path.join(REPO, n)))
              and n != "apex_tpu" and not n.startswith(".")}


def _modules(top):
    path = os.path.join(PKG, top)
    if os.path.isfile(path + ".py"):
        yield path + ".py"
        return
    for folder, dirs, names in os.walk(path):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in names:
            if name.endswith(".py"):
                yield os.path.join(folder, name)


def _imported(path):
    """Dotted names of everything ``path`` imports, relative ones made
    absolute; ``from apex_tpu import x`` counts as ``apex_tpu.x``."""
    rel = os.path.relpath(path, REPO)[:-3].split(os.sep)
    package = rel[:-1]          # a module's package; an __init__'s own
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package[:len(package) - node.level + 1]
                base = ".".join(up + ([base] if base else []))
            if base == "apex_tpu":
                for alias in node.names:
                    yield f"apex_tpu.{alias.name}"
            else:
                yield base


def _edges(top):
    """What ``top`` imports, as ``{name: set of importing files}``: its
    siblings under ``apex_tpu``, and modules of the repo's root."""
    siblings, roots = {}, {}
    for path in _modules(top):
        rel = os.path.relpath(path, REPO).replace(os.sep, "/")
        for name in _imported(path):
            parts = name.split(".")
            if parts[0] == "apex_tpu":
                if len(parts) > 1 and parts[1] != top:
                    siblings.setdefault(parts[1], set()).add(rel)
            elif parts[0] in ROOT_NAMES:
                roots.setdefault(parts[0], set()).add(rel)
    return siblings, roots


def test_the_table_names_every_package():
    tops = {n[:-3] if n.endswith(".py") else n for n in os.listdir(PKG)
            if not n.startswith("_")
            and (n.endswith(".py") or os.path.isdir(os.path.join(PKG, n)))}
    assert tops == set(ALLOWED)


@pytest.mark.parametrize("top", sorted(ALLOWED))
def test_package_imports_only_from_its_row(top):
    siblings, roots = _edges(top)
    extra = {n: sorted(siblings[n]) for n in set(siblings) - ALLOWED[top]}
    assert not extra, f"apex_tpu/{top} imports outside its row: {extra}"
    extra = {n: sorted(roots[n])
             for n in set(roots) - ROOT_ALLOWED.get(top, set())}
    assert not extra, f"apex_tpu/{top} imports the repo's root: {extra}"
    for (src, dst), files in ONLY_FROM.items():
        if src == top:
            assert siblings.get(dst, set()) <= files, \
                f"{src} -> {dst} outside {sorted(files)}"
