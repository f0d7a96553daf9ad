"""What the benchmark takes from the program stays in the program.

``benchmarks/`` (what ``BENCHMARK.json`` runs) and ``chip_smoke.py`` are
read with ``ast`` and never imported: every name they import from
``dlrover_tpu``, every attribute they read off a ``dlrover_tpu`` module
they imported, and every ``DLROVER_TPU_*`` variable they mention must
still be there.  A PR that may not edit ``benchmarks/`` learns here, on
the CPU, that it took away something a cell uses, and not from a cell
that fails on the chip.
"""

import ast
import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "dlrover_tpu"


def _consumers():
    found = glob.glob(
        os.path.join(ROOT, "benchmarks", "**", "*.py"), recursive=True
    )
    return sorted(found) + [os.path.join(ROOT, "chip_smoke.py")]


def _module_file(module):
    """The file of a dotted module of the tree, or None."""
    base = os.path.join(ROOT, *module.split("."))
    for path in (base + ".py", os.path.join(base, "__init__.py")):
        if os.path.isfile(path):
            return path
    return None


@functools.cache
def _bound_names(module_file):
    """Every name a module binds at its top level: definitions,
    assignments and imports (which is how a package re-exports), those
    under a top-level ``if``/``try``/``with`` included."""
    with open(module_file) as f:
        tree = ast.parse(f.read())
    names = set()
    todo = list(tree.body)
    while todo:
        node = todo.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            for alias in node.names:
                names.add((alias.asname or alias.name).split(".")[0])
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target]
            )
            for target in targets:
                names.update(
                    n.id for n in ast.walk(target) if isinstance(n, ast.Name)
                )
        elif isinstance(node, (ast.If, ast.Try, ast.With, ast.For,
                               ast.While)):
            for field in ("body", "orelse", "finalbody"):
                todo.extend(getattr(node, field, []))
            for handler in getattr(node, "handlers", []):
                todo.extend(handler.body)
    return names


def _provides(module, name):
    """Whether ``from module import name`` would find something: a
    submodule, or a name the module binds."""
    if _module_file(f"{module}.{name}"):
        return True
    module_file = _module_file(module)
    return bool(module_file) and name in _bound_names(module_file)


def _surface():
    """(pairs, env names): ``(module, name)`` for every name imported
    from the package and every attribute read off an imported module of
    it (``name`` None for a bare ``import module``), and every
    ``DLROVER_TPU_*`` the consumers' text holds."""
    pairs, env_names = set(), set()
    for path in _consumers():
        with open(path) as f:
            source = f.read()
        env_names.update(re.findall(r"\bDLROVER_TPU_[A-Z0-9_]+\b", source))
        tree = ast.parse(source)
        aliases = {}  # local name -> the module of the package it is
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.level == 0 and (
                (node.module or "").split(".")[0] == PACKAGE
            ):
                for alias in node.names:
                    pairs.add((node.module, alias.name))
                    if _module_file(f"{node.module}.{alias.name}"):
                        aliases[alias.asname or alias.name] = (
                            f"{node.module}.{alias.name}"
                        )
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.split(".")[0] == PACKAGE:
                        pairs.add((alias.name, None))
                        if alias.asname:
                            aliases[alias.asname] = alias.name
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and isinstance(
                node.value, ast.Name
            ) and node.value.id in aliases:
                pairs.add((aliases[node.value.id], node.attr))
    return sorted(pairs, key=lambda p: (p[0], p[1] or "")), sorted(env_names)


@functools.cache
def _registered_env_names():
    """Names ``common/envs.py`` registers, by their text or through a
    constant of ``common/constants.py`` (``NodeEnv.JOB_NAME``), with the
    ``NodeEnv`` and ``ConfigPath`` constants themselves, read off the
    syntax trees."""
    constants = {}
    with open(_module_file(f"{PACKAGE}.common.constants")) as f:
        for node in ast.parse(f.read()).body:
            if not isinstance(node, ast.ClassDef):
                continue
            for stmt in node.body:
                if isinstance(stmt, ast.Assign) and isinstance(
                    stmt.value, ast.Constant
                ):
                    constants[(node.name, stmt.targets[0].id)] = (
                        stmt.value.value
                    )
    names = {
        value for (owner, _), value in constants.items()
        if owner in ("NodeEnv", "ConfigPath")
        and str(value).startswith("DLROVER_TPU_")
    }
    with open(_module_file(f"{PACKAGE}.common.envs")) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and getattr(
            node.func, "id", ""
        ) == "register" and node.args:
            first = node.args[0]
            if isinstance(first, ast.Constant):
                names.add(first.value)
            elif isinstance(first, ast.Attribute):
                names.add(
                    constants[(getattr(first.value, "id", ""), first.attr)]
                )
    return names


PAIRS, ENV_NAMES = _surface()


def test_the_surface_is_not_empty():
    """A scan that silently finds nothing would pass every case below."""
    assert len(PAIRS) >= 26 and len(ENV_NAMES) >= 15
    assert ("dlrover_tpu.trainer.train", "Trainer") in PAIRS
    assert ("dlrover_tpu.trainer.flash_checkpoint.snapshot",
            "read_snapshot_meta") in PAIRS


@pytest.mark.parametrize(
    "module,name", PAIRS, ids=[f"{m}:{n or 'module'}" for m, n in PAIRS]
)
def test_imported_name_is_in_the_tree(module, name):
    assert _module_file(module), (
        f"benchmarks/ or chip_smoke.py imports {module}: no such module"
    )
    if name is not None:
        assert _provides(module, name), (
            f"benchmarks/ or chip_smoke.py takes `{name}` from {module}, "
            "which neither defines nor re-exports it"
        )


@pytest.mark.parametrize("env_name", ENV_NAMES)
def test_mentioned_env_name_is_registered(env_name):
    assert env_name in _registered_env_names(), (
        f"benchmarks/ or chip_smoke.py mentions {env_name}, which "
        "common/envs.py does not register"
    )
