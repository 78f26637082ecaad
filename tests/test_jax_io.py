"""JAX-facing checkpoint contract (single process; multi-rank behaviour is
covered by the numpy-level tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.jax_io import layout_from_jax, load_jax, save_jax, tree_names
from repro.core.store import DatasetStore
from repro.core.tensor_ckpt import TensorCheckpoint


def _tree():
    k = jax.random.PRNGKey(0)
    return {
        "params": {
            "embed": jax.random.normal(k, (32, 8), dtype=jnp.float32),
            "layers": [jnp.arange(12, dtype=jnp.int32).reshape(3, 4),
                       jnp.ones((5,), dtype=jnp.bfloat16)],
        },
        "step": jnp.array(7, dtype=jnp.int32),
    }


def test_tree_names_stable():
    names, leaves, _ = tree_names(_tree())
    assert names == ["params/embed", "params/layers/0", "params/layers/1",
                     "step"]


def _joined_key_entries(path) -> str:
    """Reference leaf name: each key entry's own key, index or attribute
    name, "/"-joined — the names every stored checkpoint was written with."""
    parts = []
    for entry in path:
        for attr in ("key", "idx", "name"):
            if hasattr(entry, attr):
                parts.append(str(getattr(entry, attr)))
                break
        else:
            parts.append(str(entry))
    return "/".join(parts)


class _Pair(tuple):
    """A custom pytree node: its children get FlattenedIndexKey paths."""


jax.tree_util.register_pytree_node(
    _Pair, lambda p: (list(p), None), lambda _, xs: _Pair(xs))


def test_tree_names_match_key_entry_join():
    """Leaf names are byte-identical to the key-entry join for every key
    kind: DictKey (str and int keys), SequenceKey, GetAttrKey and
    FlattenedIndexKey."""
    import collections

    Opt = collections.namedtuple("Opt", ["mu", "nu"])
    one = jnp.ones((2,), jnp.float32)
    tree = {
        "params/wq": one,
        "opt": Opt(mu={"w": one, "b": one}, nu=[one, (one, None, one)]),
        "layers": {7: _Pair([one, {"deep": [one]}]), 10: one},
    }
    names, leaves, _ = tree_names(tree)
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    assert names == [_joined_key_entries(p) for p, _ in flat]
    assert names == ["layers/7/0", "layers/7/1/deep/0", "layers/10",
                     "opt/mu/b", "opt/mu/w", "opt/nu/0", "opt/nu/1/0",
                     "opt/nu/1/2", "params/wq"]
    assert len(leaves) == len(names)


def test_jax_roundtrip(tmp_path):
    tree = _tree()
    store = DatasetStore(str(tmp_path), "w")
    ck = TensorCheckpoint(store)
    ck.save_layout(layout_from_jax(tree))
    save_jax(ck, tree, step=0)
    target = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree)
    loaded = jax.tree.map(np.asarray, load_jax(ck, target, step=0))
    ref = jax.tree.map(np.asarray, tree)
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(a, b)


def test_jax_bf16_bytes_exact(tmp_path):
    tree = {"w": jnp.asarray(np.random.default_rng(0).normal(size=(16, 4)),
                             dtype=jnp.bfloat16)}
    store = DatasetStore(str(tmp_path), "w")
    ck = TensorCheckpoint(store)
    ck.save_layout(layout_from_jax(tree))
    save_jax(ck, tree, step=3)
    target = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding),
        tree)
    loaded = load_jax(ck, target, step=3)
    assert loaded["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(loaded["w"], dtype=np.float32),
                                  np.asarray(tree["w"], dtype=np.float32))
