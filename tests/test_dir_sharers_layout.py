"""The full-map sharer store (SimState.dir_sharers): flat, entry-major
uint32 words, read and written only through state.sharers_gather and
state.sharers_add.  Each helper is held to a numpy model of the
[W*A, F] uint64 plane layout the store replaced (plane (w, way) at row
w*A + way), which checkpoints of schema <= 27 still carry."""

from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from graphite_tpu.config import load_config
from graphite_tpu.engine.state import (dir_sharers_view, make_state,
                                       sharer_words, sharers_add,
                                       sharers_from_planes, sharers_gather)
from graphite_tpu.params import SimParams

A = 4          # directory ways
DSETS = 2      # directory sets per home tile


def _planes(rng, T):
    """Random [W*A, F] uint64 planes of the old layout."""
    W = sharer_words(T)
    return rng.integers(0, 2**63, size=(W * A, T * DSETS), dtype=np.uint64) \
        * np.uint64(2) + rng.integers(0, 2, size=(W * A, T * DSETS),
                                      dtype=np.uint64)


def _rows_of(planes, fidx):
    """Old-layout gather: [N, A, W] uint64 rows of entries ``fidx``."""
    W = planes.shape[0] // A
    return planes[:, fidx].reshape(W, A, -1).transpose(2, 1, 0)


def _store(planes):
    return jnp.asarray(sharers_from_planes(planes, A))


def _view(sh, T):
    state = SimpleNamespace(dir_sharers=sh,
                            dir_word=np.zeros(T * DSETS * A * 2, np.uint32))
    return np.asarray(dir_sharers_view(state, A))


def _as_planes(sh, T):
    """Flat store -> [W*A, F] planes, through dir_sharers_view."""
    v = _view(sh, T)                                   # [A, F, W]
    return np.moveaxis(v, 2, 0).reshape(-1, v.shape[1]).copy()


@pytest.mark.parametrize("T", [8, 64, 100, 1024])
def test_store_word_order_and_view(T):
    """Word j of entry (fidx, way) is bits 32j..32j+31 of its row, at
    (fidx*A + way)*2W + j; the view gives back the old planes."""
    rng = np.random.default_rng(T)
    planes = _planes(rng, T)
    W = sharer_words(T)
    flat = sharers_from_planes(planes, A)
    assert flat.dtype == np.uint32 and flat.shape == (planes.size * 2,)
    for _ in range(20):
        f = int(rng.integers(0, T * DSETS))
        way, w = int(rng.integers(0, A)), int(rng.integers(0, W))
        u = int(planes[w * A + way, f])
        base = (f * A + way) * 2 * W
        assert int(flat[base + 2 * w]) == u & 0xFFFFFFFF
        assert int(flat[base + 2 * w + 1]) == u >> 32
    view = _view(jnp.asarray(flat), T)
    np.testing.assert_array_equal(
        view, np.moveaxis(planes.reshape(W, A, -1), 0, -1))


@pytest.mark.parametrize("T", [8, 64, 100, 1024])
@pytest.mark.parametrize("one_way", [False, True])
def test_gather_matches_planes(T, one_way):
    """All ways of each entry, or the row of one way per entry."""
    rng = np.random.default_rng(T + 1)
    planes = _planes(rng, T)
    fidx = rng.integers(0, T * DSETS, size=T).astype(np.int32)
    want = _rows_of(planes, fidx)
    way = None
    if one_way:
        way = rng.integers(0, A, size=T).astype(np.int32)
        want = want[np.arange(T), way]
    got = np.asarray(sharers_gather(
        _store(planes), jnp.asarray(fidx), A, sharer_words(T),
        None if way is None else jnp.asarray(way)))
    np.testing.assert_array_equal(got, want)


def _model_add(planes, rows=None, bits=None, clear_bits=False):
    """The old layout's merged scatter-add, in numpy (uint64 wraparound)."""
    out = planes.copy()
    W = planes.shape[0] // A
    with np.errstate(over="ignore"):
        if rows is not None:
            mask, fidx, way, old, new = rows
            for n in np.flatnonzero(mask):
                for w in range(W):
                    out[w * A + way[n], fidx[n]] += new[n, w] - old[n, w]
        if bits is not None:
            mask, fidx, way, tile = bits
            for n in np.flatnonzero(mask):
                bit = np.uint64(1) << np.uint64(tile[n] % 64)
                r = (tile[n] // 64) * A + way[n]
                if clear_bits:
                    out[r, fidx[n]] -= bit
                else:
                    out[r, fidx[n]] += bit
    return out


def _apply(planes, T, **kw):
    args = {k: (tuple(jnp.asarray(x) for x in v) if isinstance(v, tuple)
                else v) for k, v in kw.items()}
    sh = sharers_add(_store(planes), A, sharer_words(T), **args)
    assert sh.dtype == jnp.uint32 and sh.shape == (planes.size * 2,)
    return _as_planes(sh, T)


def _slots(rng, T, n):
    """n distinct (fidx, way) slots, as the directory election gives."""
    flat = rng.choice(T * DSETS * A, size=n, replace=False)
    return (flat // A).astype(np.int32), (flat % A).astype(np.int32)


@pytest.mark.parametrize("T", [8, 64, 100, 1024])
@pytest.mark.parametrize("case", ["rewrite", "rewrite_and_bits", "wipe",
                                  "clear_bits"])
def test_add_matches_planes(T, case):
    """Row rewrites (old -> new), wipes (new = 0) and single-bit adds or
    clears land exactly what the old layout's scatter-add landed."""
    rng = np.random.default_rng(T * 7 + len(case))
    planes = _planes(rng, T)
    W = sharer_words(T)
    n = min(T, 16)
    fidx, way = _slots(rng, T, n)
    mask = rng.random(n) < 0.7
    old = _rows_of(planes, fidx)[np.arange(n), way]               # [n, W]
    new = _planes(rng, T)[:W, :n].T.copy()                        # [n, W]
    tile = rng.integers(0, T, size=n).astype(np.int32)
    bit = np.uint64(1) << (tile % 64).astype(np.uint64)
    has = (new[np.arange(n), tile // 64] & bit) != 0
    kw = {}
    if case == "rewrite":
        kw["rows"] = (mask, fidx, way, old, new)
    elif case == "rewrite_and_bits":
        # Combined winners add their (clear) bit on top of the new row.
        kw["rows"] = (mask, fidx, way, old, new)
        kw["bits"] = (mask & ~has, fidx, way, tile)
    elif case == "wipe":
        kw["rows"] = (mask, fidx, way, old, np.zeros_like(old))
    else:
        own = (old[np.arange(n), tile // 64] & bit) != 0
        kw["bits"] = (mask & own, fidx, way, tile)
        kw["clear_bits"] = True
    np.testing.assert_array_equal(_apply(planes, T, **kw),
                                  _model_add(planes, **kw))
    if case == "wipe":
        got = _rows_of(_apply(planes, T, **kw), fidx)[np.arange(n), way]
        assert (got[mask] == 0).all()


def test_rewrite_whose_low_half_borrows():
    """old 0x1_0000_0000 -> new 0xFFFF_FFFF: the uint64 delta's low half
    is 0xFFFF_FFFF with a borrow out of it, so splitting that delta into
    halves would leave 0x1_FFFF_FFFF; per-word deltas land the new row."""
    T = 64
    planes = np.zeros((A, T * DSETS), np.uint64)
    planes[2, 5] = 0x1_0000_0000
    rows = (np.array([True]), np.array([5], np.int32),
            np.array([2], np.int32), np.array([[0x1_0000_0000]], np.uint64),
            np.array([[0xFFFF_FFFF]], np.uint64))
    got = _apply(planes, T, rows=rows)
    assert int(got[2, 5]) == 0xFFFF_FFFF
    got[2, 5] = 0
    assert not got.any()


@pytest.mark.parametrize("tile", [31, 32, 63, 64])
def test_bit_add_then_clear(tile):
    """A single bit touches one u32 word, tile // 32, on either side of
    the 32- and 64-bit word edges."""
    T = 128
    W = sharer_words(T)
    planes = np.zeros((W * A, T * DSETS), np.uint64)
    bits = (np.array([True]), np.array([9], np.int32),
            np.array([1], np.int32), np.array([tile], np.int32))
    sh = sharers_add(_store(planes), A, W,
                     bits=tuple(jnp.asarray(x) for x in bits))
    flat = np.asarray(sh)
    assert np.flatnonzero(flat).tolist() == [(9 * A + 1) * 2 * W + tile // 32]
    assert int(flat[(9 * A + 1) * 2 * W + tile // 32]) == 1 << (tile % 32)
    want = np.zeros_like(planes)
    want[(tile // 64) * A + 1, 9] = np.uint64(1) << np.uint64(tile % 64)
    np.testing.assert_array_equal(_as_planes(sh, T), want)
    sh = sharers_add(sh, A, W, bits=tuple(jnp.asarray(x) for x in bits),
                     clear_bits=True)
    assert not np.asarray(sh).any()


def test_dropped_updates_leave_store_untouched():
    """Masked-off updates index one past the end and drop, wherever
    their fidx points (the last entry included)."""
    T = 64
    rng = np.random.default_rng(3)
    planes = _planes(rng, T)
    W = sharer_words(T)
    n = 8
    fidx = np.full(n, T * DSETS - 1, np.int32)
    way = np.full(n, A - 1, np.int32)
    no = np.zeros(n, bool)
    old = _rows_of(planes, fidx)[np.arange(n), way]
    sh0 = _store(planes)
    sh = sharers_add(sh0, A, W,
                     rows=tuple(jnp.asarray(x) for x in
                                (no, fidx, way, old, old + np.uint64(1))),
                     bits=tuple(jnp.asarray(x) for x in
                                (no, fidx, way, np.arange(n, dtype=np.int32))))
    np.testing.assert_array_equal(np.asarray(sh), np.asarray(sh0))


def test_store_past_int32_index_range_is_refused():
    """F*A*2W >= 2**31 words cannot be indexed with int32: make_state
    refuses before it allocates (1,024 tiles, 4,096 sets x 16 ways a
    slice: exactly 2**31 words)."""
    cfg = load_config()
    cfg.set("general/total_cores", 1024)
    cfg.set("dram_directory/total_entries", 65536)
    params = SimParams.from_config(cfg)
    with pytest.raises(ValueError, match="int32 index range"):
        make_state(params, has_capi=False)
