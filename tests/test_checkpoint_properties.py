"""Property tests (hypothesis) for checkpoint save/restore round-trips.

Skipped entirely when ``hypothesis`` is not installed (install the
``test`` extra); deterministic equivalents of the core round-trip /
mismatch behaviors always run in ``test_checkpoint.py``.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro import checkpoint as ckpt  # noqa: E402

_KEYS = st.text(alphabet="abcdefgh", min_size=1, max_size=4)
_SHAPES = st.lists(st.integers(1, 4), min_size=0, max_size=3).map(tuple)
_DTYPES = st.sampled_from([np.float32, np.int32, np.uint32, np.float64])


@st.composite
def leaves(draw):
    shape = draw(_SHAPES)
    dtype = draw(_DTYPES)
    n = int(np.prod(shape, dtype=np.int64)) if shape else 1
    # each dtype draws only values it can hold (NumPy 2 rejects -1 as uint)
    lo = 0 if np.issubdtype(dtype, np.unsignedinteger) else -1000
    vals = draw(st.lists(
        st.integers(lo, 1000), min_size=n, max_size=n))
    return np.asarray(vals, dtype=dtype).reshape(shape)


def trees(depth=2):
    leaf = leaves()
    if depth == 0:
        return leaf
    return st.dictionaries(_KEYS, st.one_of(leaf, trees(depth - 1)),
                           min_size=1, max_size=3)


@given(tree=trees(), step=st.integers(0, 10**7))
@settings(max_examples=30, deadline=None)
def test_save_restore_roundtrip(tmp_path_factory, tree, step):
    d = str(tmp_path_factory.mktemp("ck"))
    ckpt.save(d, step, tree)
    assert ckpt.latest_step(d) == step
    zeros = {}  # restore_tree needs no template — compare straight
    del zeros
    out, _ = ckpt.restore_tree(d)
    flat_in = ckpt._flatten_with_paths(tree)
    flat_out = ckpt._flatten_with_paths(out)
    assert flat_in[0] == flat_out[0]
    for a, b in zip(flat_in[1], flat_out[1]):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)


@given(tree=trees(), step=st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_restore_into_zeroed_template(tmp_path_factory, tree, step):
    import jax

    d = str(tmp_path_factory.mktemp("ck"))
    ckpt.save(d, step, tree)
    template = jax.tree.map(np.zeros_like, tree)
    out = ckpt.restore(d, template)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@given(tree=st.dictionaries(_KEYS, leaves(), min_size=2, max_size=4),
       data=st.data())
@settings(max_examples=20, deadline=None)
def test_renamed_leaf_always_raises(tmp_path_factory, tree, data):
    d = str(tmp_path_factory.mktemp("ck"))
    ckpt.save(d, 1, tree)
    old = data.draw(st.sampled_from(sorted(tree)))
    bad = dict(tree)
    bad[old + "_renamed"] = bad.pop(old)
    with pytest.raises(ValueError):
        ckpt.restore(d, bad)
