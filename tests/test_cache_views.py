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


def test_the_seam_declares_seven_kinds():
    assert sorted(DECLARED) == ["kv", "latent", "none", "recurrent",
                                "shared", "sparse", "window"]


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


# ------------------------------------ the arena's second row width (sparse)


def _sparse_arena(num_blocks=64, dtype="bfloat16"):
    """The arena of ``serve-longctx-sparse-moe``'s eight layers (4 K/V
    heads of 128 and an index key of 64 a token, blocks of 16), at fewer
    blocks: as the engine builds it from the table's ``pool_row``."""
    from paddle_tpu.serving.kv_arena import KVArena

    st = seam.SparseKVLayerState(32, 128, 4, 64, 16, 2048)
    row = cv.pool_row((st,) * 8)
    assert row == (4, 128, 0, 64)  # (heads, head_dim, latent, index width)
    return KVArena(8, row[0], row[1], num_blocks, 16, dtype, False, None, 32,
                   (), *row[2:])


def test_a_sparse_entry_is_three_arrays_and_counted_whole():
    """K, V and the index keys packed two to a 128-lane row, in the same
    blocks; ``bytes_total`` and the bytes a token count all three: 17,408
    B a token over the usable blocks (ISSUE 48), the index pool 128 B a
    token a layer of it; the gauge ``arena.index_bytes`` says so."""
    from paddle_tpu.serving import metrics

    arena = _sparse_arena()
    assert arena.index_width == 64 and arena.latent_width == 0
    k, v, index = arena.pools[0]
    assert k.shape == v.shape == (64, 16, 4, 128)
    assert index.shape == (64, 8, 128) and str(index.dtype) == "bfloat16"
    arena.check_invariants()
    assert arena.bytes_total() == 64 * 16 * 17408
    tokens = arena.stats()["blocks_total"] * arena.block_size
    assert arena.bytes_total() / tokens == 17408 * 64 / 63
    by = arena.bytes_by_namespace()["primary"]
    assert by["scale_bytes"] == 0 and by["kv_bytes"] == arena.bytes_total()
    assert metrics.gauges()["arena.index_bytes"] == 8 * 64 * 16 * 128


def test_a_third_array_is_taken_for_neither_bf16_nor_int8():
    """The entry's tuple length is structure (2: K and V; 4: int8 with
    scales): an entry adopted without its index keys fails the audit, and
    the ``"kv"`` kind's writers refuse three arrays instead of reading the
    index pool as a scale pool."""
    import jax.numpy as jnp

    arena = _sparse_arena(num_blocks=4, dtype="float32")
    arena.set_pools([entry[:2] for entry in arena.pools])
    with pytest.raises(RuntimeError, match="expected 3"):
        arena.check_invariants()
    entry = _sparse_arena(num_blocks=4, dtype="float32").pools[0]
    row = jnp.zeros((1, 4, 128), jnp.float32)
    with pytest.raises(ValueError):
        cv.scatter_rows(entry, jnp.asarray([1]), jnp.asarray([0]), row, row)
    with pytest.raises(ValueError, match="int8 form"):
        from paddle_tpu.serving.kv_arena import KVArena

        KVArena(1, 4, 128, 4, 16, "float32", True, None, 1, (), 0, 64)
    with pytest.raises(ValueError, match="not both"):
        KVArena(1, 4, 128, 4, 16, "float32", False, None, 1, (), 576, 64)


def test_sparse_commit_writes_three_pools_in_whole_blocks():
    """A prompt of 20 tokens under a bucket of 32 and blocks of 16: K, V
    and index keys of the first 20 positions land in the slot's two
    blocks, the padding block in scratch block 0, nothing elsewhere."""
    import jax.numpy as jnp
    import numpy as np

    arena = _sparse_arena(num_blocks=6, dtype="float32")
    entry = arena.pools[0]
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.standard_normal((1, 48, 4, 128)), jnp.float32)
    ki = jnp.asarray(rng.standard_normal((1, 48, 64)), jnp.float32)
    view = cv.SparsePrefillView(2048, kept=(k, k + 1, ki))
    ctx = cv.PrefillContext(0, jnp.int32(20), 16, False, False, None)
    kp, vp, ip = cv.KINDS["sparse"].commit(
        view, entry, jnp.asarray([4, 2, 5]), ctx)
    assert np.array_equal(kp[4], k[0, :16]) and np.array_equal(kp[2],
                                                               k[0, 16:32])
    assert np.array_equal(vp[2], k[0, 16:32] + 1)
    assert np.array_equal(ip[4].reshape(16, 64), ki[0, :16])
    assert np.array_equal(ip[2].reshape(16, 64), ki[0, 16:32])
    assert not np.asarray(kp[5]).any() and not np.asarray(ip[5]).any()
    assert not np.asarray(kp[1]).any() and not np.asarray(kp[3]).any()
    assert np.array_equal(ip[0].reshape(16, 64), ki[0, 32:])  # scratch


def test_the_handoff_is_refused_for_a_sparse_model():
    from paddle_tpu.models.keye import KeyeForCausalLM, keye_tiny
    from paddle_tpu.serving.disagg import DisaggReplicaPool

    with pytest.raises(ValueError, match="handoff") as err:
        DisaggReplicaPool(KeyeForCausalLM(keye_tiny()), prefill_replicas=1,
                          decode_replicas=1)
    assert "sparse-attention layers" in str(err.value)
