"""The one module that knows a kind of layer state
(:mod:`paddle_tpu.serving.cache_views`): its table has an entry for every
kind the seam declares and for nothing else, and the engine and the
disaggregated pool ask it instead of naming a kind themselves."""
import ast
import dataclasses
import os

import pytest

from paddle_tpu.models import serving_seam as seam
from paddle_tpu.serving import cache_views as cv

#: the state declarations of the seam: its dataclasses with a ``kind``
DECLARED = {
    f.default: cls
    for cls in vars(seam).values() if dataclasses.is_dataclass(cls)
    for f in dataclasses.fields(cls) if f.name == "kind"}


def test_the_seam_declares_six_kinds():
    assert sorted(DECLARED) == ["kv", "latent", "none", "recurrent",
                                "shared", "window"]


@pytest.mark.parametrize("kind", sorted(DECLARED))
def test_every_declared_kind_has_exactly_one_table_entry(kind):
    """A kind of the seam is a key of the table, the table has no key the
    seam does not declare, and an entry says what the server needs of a
    kind that lies where it says it does: the arena's row and the decode
    kernel's minor dimension for the block pools, the store's arrays and
    a byte gauge for the slot store, neither for a layer with no state;
    whoever refuses an option says what to call its layers and why."""
    assert set(cv.KINDS) == set(DECLARED)
    entry = cv.KINDS[kind]
    assert callable(entry.decode_view) and callable(entry.prefill_view)
    assert entry.store in (cv.PAGED, cv.SLOT, None)
    paged, slot = entry.store == cv.PAGED, entry.store == cv.SLOT
    assert (entry.pool_row is not None) == paged
    assert (entry.minor is not None) == paged
    assert (entry.arrays is not None) == slot
    assert (entry.bytes_gauge is not None) == slot
    assert not entry.block_writes or paged
    assert bool(entry.refuses) == bool(entry.called) == bool(entry.why)
    # a layer without state of its own is handed no entry and commits none
    if entry.store is None:
        assert entry.commit(None, None, None, None) is None


def _kind_comparisons(tree):
    """Comparisons against a kind's name, and ``isinstance`` of a view
    class, in a module's syntax tree: ``(line, what)`` each."""
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Compare):
            for side in [node.left] + node.comparators:
                names = [c.value for c in ast.walk(side)
                         if isinstance(c, ast.Constant)]
                found += [(node.lineno, f"compares with {n!r}")
                          for n in names if n in DECLARED]
        elif (isinstance(node, ast.Call)
              and getattr(node.func, "id", None) == "isinstance"
              and "View" in ast.unparse(node.args[1])):
            found.append((node.lineno, ast.unparse(node)))
    return found


@pytest.mark.parametrize("path", ["serving/engine.py",
                                  "serving/disagg/pool.py"])
def test_no_kind_is_named_outside_the_table(path):
    """The engine and the disaggregated pool compare nothing with a
    kind's name and ask no view for its class: what differs by kind is
    the table's to say."""
    with open(os.path.join(os.path.dirname(seam.__file__), "..",
                           path)) as f:
        tree = ast.parse(f.read())
    assert _kind_comparisons(tree) == []


def test_the_finder_finds_what_it_looks_for():
    tree = ast.parse(
        "if st.kind == 'kv' or kind in ('recurrent', 'window'): pass\n"
        "x = isinstance(chunk, _LatentPrefillView)\n"
        "y = store == 'paged' and isinstance(q, Tensor)\n")
    assert sorted(line for line, _ in _kind_comparisons(tree)) == [1, 1, 1, 2]
