"""The device backend's two modules import one way: ``tpu`` ->
``tilestore``, and neither reaches up into the engine that calls the
backend, nor imports Pallas (no process that imports the backend pays
for it). Read from the source by ``ast``, so an import inside a function
counts like one at the top."""

import ast
import pathlib

import pytest

QUERY = pathlib.Path(__file__).resolve().parent.parent / "filodb_tpu" / "query"
LAYERS = ("tpu", "tilestore")


def _imports(module):
    """[(dotted name, at module level)] of every import in the file; a
    ``from a.b import c`` is listed as ``a.b`` and as ``a.b.c``."""
    tree = ast.parse((QUERY / f"{module}.py").read_text())
    top = {id(n) for n in tree.body}
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out += [(a.name, id(node) in top) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            out.append((base, id(node) in top))
            out += [(f"{base}.{a.name}", id(node) in top)
                    for a in node.names]
    return out


@pytest.mark.parametrize("module,forbidden", [
    ("tilestore", "jax.experimental.pallas"),
    ("tpu", "jax.experimental.pallas"),
    ("tilestore", "filodb_tpu.query.tpu"),
    ("tilestore", "filodb_tpu.query.engine"),
    ("tpu", "filodb_tpu.query.engine"),
])
def test_layer_does_not_import(module, forbidden):
    hits = [name for name, _ in _imports(module)
            if name == forbidden or name.startswith(forbidden + ".")]
    assert not hits, f"{module}.py imports {hits}"


def test_layers_import_each_other_at_module_level_only():
    """With no ring there is nothing to dodge: the arrows that exist are
    module-level imports, which a reader and the lint's call graph see."""
    layer_names = {f"filodb_tpu.query.{m}" for m in LAYERS}
    arrows = set()
    for module in LAYERS:
        for name, at_top in _imports(module):
            if name in layer_names:
                assert at_top, f"{module}.py imports {name} in a function"
                arrows.add((module, name.rsplit(".", 1)[1]))
    assert arrows == {("tpu", "tilestore")}
