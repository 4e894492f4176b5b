"""Layered-runtime contracts: the ifunc re-export guarantee and the
cross-layer import hygiene of the `core/pe` package.

Two things a refactor must never silently break:

* every name historically importable from ``repro.core.ifunc`` (the
  pre-split god-object) keeps importing from there — downstream code and
  older notebooks depend on that surface;
* no module outside ``repro.core.pe`` imports a private ``_``-prefixed
  symbol from a layer module (enforced by walking every AST in src/,
  tests/, and examples/), and the layers themselves only share their
  public surface with each other — the facade composes layers, nothing
  reaches around it.
"""

import ast
import importlib
import pathlib

REPO = pathlib.Path(__file__).resolve().parent.parent
SCAN_DIRS = ("src", "tests", "examples")
CORE_PACKAGE = REPO / "src" / "repro" / "core"
PE_PACKAGE = CORE_PACKAGE / "pe"
LAYER_MODULES = ("source", "wire", "codecache", "exec", "progress", "cq", "pe")


def _py_files():
    for d in SCAN_DIRS:
        root = REPO / d
        if root.exists():
            yield from sorted(root.rglob("*.py"))


def _pe_imports(tree: ast.AST, in_package: bool):
    """Yield (module, imported_name) for every from-import that resolves
    into the repro.core.pe package."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.ImportFrom):
            continue
        mod = node.module or ""
        absolute = mod.startswith("repro.core.pe")
        relative = in_package and node.level >= 1 and (
            mod.split(".")[0] in LAYER_MODULES or mod == ""
        )
        if absolute or relative:
            for alias in node.names:
                yield mod, alias.name


class TestIfuncReexports:
    def test_canonical_imports_still_work(self):
        from repro.core.ifunc import (  # noqa: F401
            PE,
            CompletionQueue,
            GatherFuture,
            IFunc,
        )

    def test_full_historical_surface(self):
        """Everything the pre-split module exported by name resolves."""
        mod = importlib.import_module("repro.core.ifunc")
        for name in (
            "ACTION_WIDTH", "A_DONE", "A_FORWARD", "A_RETURN", "A_SPAWN",
            "A_NOP", "A_PUBLISH", "CompletionQueue", "GatherFuture",
            "IFunc", "ISAMismatch", "PE", "PEStats", "ProtocolError",
            "RNDV_STAGING_DEPTH", "Toolchain",
        ):
            assert hasattr(mod, name), f"repro.core.ifunc lost {name!r}"

    def test_facade_is_thin(self):
        """The god-object stays dead: the facade module holds re-exports
        only (no class/function definitions) and stays small."""
        path = REPO / "src" / "repro" / "core" / "ifunc.py"
        text = path.read_text()
        assert len(text.splitlines()) < 200
        tree = ast.parse(text)
        defs = [
            n for n in tree.body
            if isinstance(n, (ast.ClassDef, ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        assert not defs, f"ifunc.py regrew definitions: {[d.name for d in defs]}"

    def test_layer_modules_import_independently(self):
        for layer in LAYER_MODULES:
            importlib.import_module(f"repro.core.pe.{layer}")


class TestImportHygiene:
    def test_no_private_imports_from_layers_outside_package(self):
        """No module outside core/pe/ may import a ``_``-prefixed symbol
        from any layer module — the layers' private surface is internal."""
        offenders = []
        for path in _py_files():
            if PE_PACKAGE in path.parents:
                continue
            tree = ast.parse(path.read_text())
            for mod, name in _pe_imports(tree, in_package=False):
                if name.startswith("_"):
                    offenders.append(f"{path}: from {mod} import {name}")
        assert not offenders, "\n".join(offenders)

    def test_no_private_imports_between_layers(self):
        """Within core/pe/, layers compose through public names only: a
        layer importing another layer's ``_``-prefixed symbol couples to
        its internals and defeats the layering."""
        offenders = []
        for path in sorted(PE_PACKAGE.glob("*.py")):
            tree = ast.parse(path.read_text())
            for mod, name in _pe_imports(tree, in_package=True):
                if name.startswith("_"):
                    offenders.append(f"{path.name}: from {mod} import {name}")
        assert not offenders, "\n".join(offenders)

    def test_core_never_imports_runtime_or_launch(self):
        """``repro.core`` is the bottom of the stack: no core module may
        import from ``repro.runtime`` or ``repro.launch`` — not even a
        deferred (function-level) import, which is how the inversion last
        crept in (the failure detector reaching up for the heartbeat
        monitor).  The walk covers every statement in every core module,
        absolute and relative spellings alike."""
        offenders = []
        for path in sorted(CORE_PACKAGE.rglob("*.py")):
            # the package this file's relative imports resolve against
            pkg = ["repro", "core", *path.relative_to(CORE_PACKAGE).parts[:-1]]
            tree = ast.parse(path.read_text())
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    targets = [alias.name for alias in node.names]
                elif isinstance(node, ast.ImportFrom):
                    mod = node.module or ""
                    if node.level:
                        base = pkg[: len(pkg) - (node.level - 1)]
                        targets = [".".join([*base, *mod.split(".")]).rstrip(".")]
                    else:
                        targets = [mod]
                else:
                    continue
                for target in targets:
                    parts = target.split(".")
                    if parts[:1] == ["repro"] and parts[1:2] and parts[1] in (
                        "runtime", "launch"
                    ):
                        offenders.append(f"{path.relative_to(REPO)}: {target}")
        assert not offenders, (
            "repro.core must not depend on repro.runtime/repro.launch:\n"
            + "\n".join(offenders)
        )

    def test_layers_do_not_import_the_facade(self):
        """The facade composes the layers; a layer importing `.pe` back
        (outside annotations) would be a dependency cycle.  TYPE_CHECKING
        imports are fine — this walks only runtime imports."""
        for path in sorted(PE_PACKAGE.glob("*.py")):
            if path.name in ("pe.py", "__init__.py"):
                continue
            tree = ast.parse(path.read_text())
            runtime_imports = []
            for node in ast.walk(tree):
                if isinstance(node, ast.If):
                    # skip `if TYPE_CHECKING:` bodies
                    t = node.test
                    if isinstance(t, ast.Name) and t.id == "TYPE_CHECKING":
                        for sub in ast.walk(node):
                            sub._skip = True  # type: ignore[attr-defined]
            for node in ast.walk(tree):
                if getattr(node, "_skip", False):
                    continue
                if isinstance(node, ast.ImportFrom) and (node.module or "") in (
                    "pe", "repro.core.pe.pe"
                ):
                    runtime_imports.append(ast.dump(node))
            assert not runtime_imports, f"{path.name} imports the facade at runtime"
