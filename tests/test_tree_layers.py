"""The layers of ``models/trees/``: each module imports only the modules
below it, the kernels import no tracer, and the rest of the package sees
the tree package through its public names alone."""
import ast
import os

import pytest

import transmogrifai_tpu
from transmogrifai_tpu.models import trees

PACKAGE = os.path.dirname(transmogrifai_tpu.__file__)
TREES = os.path.dirname(trees.__file__)

#: the modules of the tree package, lowest first, and the ones each may
#: import: ``forms`` <- ``design`` <- ``grower`` <- ``bodies`` <-
#: ``fold_grid`` <- ``estimators``; ``forms`` <- ``walk`` <- ``fitted`` <-
#: ``fold_grid`` / ``estimators``
LAYERS = ("forms", "design", "grower", "bodies", "walk", "fitted",
          "fold_grid", "estimators")
ALLOWED = {
    "forms": set(),
    "design": {"forms"},
    "grower": {"forms", "design"},
    "bodies": {"forms", "design", "grower"},
    "walk": {"forms"},
    "fitted": {"walk"},
    "fold_grid": {"forms", "design", "grower", "bodies", "walk", "fitted"},
    "estimators": {"forms", "design", "bodies", "fitted", "fold_grid"},
}
#: what the package's other modules may take from the tree package
PUBLIC = set(trees.__all__) | {"batch_predict_raw", "clear_design_cache",
                               "tree_kernel_compiles"}


def _imports(path):
    """``(module, names)`` of every import in the file at ``path``, function
    bodies included, relative ones resolved against its package."""
    rel = os.path.relpath(path, os.path.dirname(PACKAGE))
    package = os.path.dirname(rel).replace(os.sep, ".")
    tree = ast.parse(open(path).read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield alias.name, ()
        elif isinstance(node, ast.ImportFrom):
            base = package.split(".")
            if node.level:
                base = base[:len(base) - node.level + 1]
            module = ".".join(base + ([node.module] if node.module else [])
                              ) if node.level else node.module
            names = tuple(a.name for a in node.names)
            if node.module is None:       # ``from . import forms``
                for name in names:
                    yield module + "." + name, ()
            else:
                yield module, names


def _sources(root):
    for folder, _, files in os.walk(root):
        for name in sorted(files):
            if name.endswith(".py"):
                yield os.path.join(folder, name)


def test_the_package_holds_the_layers():
    found = {name[:-3] for name in os.listdir(TREES) if name.endswith(".py")}
    assert found == set(LAYERS) | {"__init__"}
    assert not os.path.exists(os.path.join(PACKAGE, "models", "trees.py"))
    # every allowed arrow points down the order
    for module, below in ALLOWED.items():
        assert all(LAYERS.index(b) < LAYERS.index(module) for b in below)


@pytest.mark.parametrize("module", LAYERS)
def test_each_module_imports_only_the_layers_below(module):
    own = "transmogrifai_tpu.models.trees."
    imported = {name[len(own):].split(".")[0]
                for name, _ in _imports(os.path.join(TREES, module + ".py"))
                if name.startswith(own)}
    assert imported <= ALLOWED[module], module


@pytest.mark.parametrize("module", LAYERS)
def test_only_the_fold_grid_drivers_import_the_tracer(module):
    traced = [name for name, _ in _imports(os.path.join(TREES,
                                                        module + ".py"))
              if name.startswith("transmogrifai_tpu.observability")]
    assert bool(traced) is (module == "fold_grid"), traced


def test_the_rest_of_the_package_takes_public_names_only():
    """Outside ``models/trees/`` the tree package is imported by its name
    (never a module of it) and only for public names; of ``models/``, only
    its ``__init__`` and the registry import it, and ``selector/`` only for
    ``batch_predict_raw`` (``selector.py`` not at all)."""
    takers = {}
    for path in _sources(PACKAGE):
        if path.startswith(TREES + os.sep):
            continue
        for module, names in _imports(path):
            if module == "transmogrifai_tpu.models" and "trees" in names:
                takers.setdefault(path, set()).add("trees")
            if not module.startswith("transmogrifai_tpu.models.trees"):
                continue
            assert module == "transmogrifai_tpu.models.trees", (path, module)
            assert set(names) <= PUBLIC, (path, names)
            takers.setdefault(path, set()).update(names)
    rel = {os.path.relpath(p, PACKAGE).replace(os.sep, "/"): names
           for p, names in takers.items()}
    models = {p for p in rel if p.startswith("models/")}
    assert models == {"models/__init__.py", "models/registry.py"}
    selector = {p: names for p, names in rel.items()
                if p.startswith("selector/")}
    assert selector == {"selector/validator.py": {"batch_predict_raw"}}
