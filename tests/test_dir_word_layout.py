"""The packed directory store (SimState.dir_word): flat, entry-major
uint32 halves, read and written only through state.dir_gather and
state.dir_set.  Each helper is held to a numpy model of the [A, F] int64
array the store replaced (entry (f, way) at [way, f]), which checkpoints
of schemas 26-29 still carry."""

import jax.numpy as jnp
import numpy as np
import pytest

from graphite_tpu.config import load_config
from graphite_tpu.engine.state import (_gather_runs, dir_gather,
                                       dir_live_entries, dir_set,
                                       dir_word_from_2d, dir_word_view,
                                       dword_pack, dword_stamp, dword_tag,
                                       make_state)
from graphite_tpu.params import SimParams

A = 4          # directory ways
DSETS = 2      # directory sets per home tile


def _words(rng, T):
    """Random [A, F] int64 words over all 64 bits (the sign bit too)."""
    return rng.integers(0, 2**64, size=(A, T * DSETS),
                        dtype=np.uint64).view(np.int64)


def _store(words):
    return jnp.asarray(dir_word_from_2d(words))


@pytest.mark.parametrize("T", [8, 64, 1024])
def test_store_word_order_and_view(T):
    """Entry (f, way) is at (f*A + way)*2, low half first; the view gives
    the [A, F] words back."""
    rng = np.random.default_rng(T)
    words = _words(rng, T)
    flat = dir_word_from_2d(words)
    assert flat.dtype == np.uint32 and flat.shape == (words.size * 2,)
    u = words.view(np.uint64)
    for _ in range(20):
        f, way = int(rng.integers(0, T * DSETS)), int(rng.integers(0, A))
        base = (f * A + way) * 2
        assert int(flat[base]) == int(u[way, f]) & 0xFFFFFFFF
        assert int(flat[base + 1]) == int(u[way, f]) >> 32
    view = np.asarray(dir_word_view(jnp.asarray(flat), A))
    assert view.dtype == np.int64
    np.testing.assert_array_equal(view, words)


def test_leading_axes_convert_per_lane():
    """A batched [V, A, F] array converts lane by lane."""
    rng = np.random.default_rng(5)
    lanes = np.stack([_words(rng, 8) for _ in range(3)])
    flat = dir_word_from_2d(lanes)
    assert flat.shape == (3, lanes[0].size * 2)
    for v in range(3):
        np.testing.assert_array_equal(flat[v], dir_word_from_2d(lanes[v]))


@pytest.mark.parametrize("T", [8, 64, 1024])
def test_gather_matches_rows(T):
    """Every way of each entry, as the old [:, fidx].T gather gave."""
    rng = np.random.default_rng(T + 1)
    words = _words(rng, T)
    fidx = rng.integers(0, T * DSETS, size=T).astype(np.int32)
    fidx[0] = T * DSETS - 1                             # the last entry
    got = np.asarray(dir_gather(_store(words), jnp.asarray(fidx), A))
    np.testing.assert_array_equal(got, words[:, fidx].T)


@pytest.mark.parametrize("size,n", [
    (1024, 8),        # sixteen runs in each 128-word row
    (1024, 128),      # one run per row
    (2048, 256),      # runs of two whole rows
    (1000, 8),        # a store that is no whole number of rows
    (1536, 6)])       # runs that straddle rows
def test_gather_runs_match_slices(size, n):
    """Every path of the run gather reads the words a slice reads."""
    rng = np.random.default_rng(size + n)
    store = rng.integers(0, 2**32, size=size, dtype=np.uint64) \
        .astype(np.uint32)
    start = rng.integers(0, size // n, size=40) * n
    start[0] = (size // n - 1) * n                      # the last run
    got = np.asarray(_gather_runs(jnp.asarray(store), jnp.asarray(start), n))
    np.testing.assert_array_equal(
        got, np.stack([store[s:s + n] for s in start]))


def _set(words, mask, fidx, way, new):
    out = dir_set(_store(words), A, jnp.asarray(mask), jnp.asarray(fidx),
                  jnp.asarray(way), jnp.asarray(new))
    assert out.dtype == jnp.uint32 and out.shape == (words.size * 2,)
    return np.asarray(dir_word_view(out, A))


@pytest.mark.parametrize("T", [8, 64, 1024])
def test_masked_set_matches_model(T):
    """Writes land where ``mask``; masked-off ones drop, the last entry's
    included."""
    rng = np.random.default_rng(T + 2)
    words = _words(rng, T)
    n = min(T, 16)
    flat = rng.choice(T * DSETS * A, size=n, replace=False)
    fidx, way = (flat // A).astype(np.int32), (flat % A).astype(np.int32)
    fidx[-1], way[-1] = T * DSETS - 1, A - 1
    mask = rng.random(n) < 0.6
    mask[-1] = False
    new = _words(rng, T)[0, :n]
    want = words.copy()
    want[way[mask], fidx[mask]] = new[mask]
    np.testing.assert_array_equal(_set(words, mask, fidx, way, new), want)


def test_all_masked_leaves_store_untouched():
    T = 64
    words = _words(np.random.default_rng(3), T)
    n = 8
    fidx = np.full(n, T * DSETS - 1, np.int32)
    way = np.full(n, A - 1, np.int32)
    new = np.arange(n, dtype=np.int64)
    np.testing.assert_array_equal(
        _set(words, np.zeros(n, bool), fidx, way, new), words)


def test_colliding_identical_writes():
    """Combined winners of one line write the same word to one slot."""
    T = 8
    words = _words(np.random.default_rng(4), T)
    w = np.int64(-0x1234_5678_9ABC_DEF1)
    fidx = np.array([3, 3, 3, 5], np.int32)
    way = np.array([2, 2, 2, 0], np.int32)
    new = np.array([w, w, w, 7], np.int64)
    want = words.copy()
    want[2, 3], want[0, 5] = w, 7
    np.testing.assert_array_equal(
        _set(words, np.ones(4, bool), fidx, way, new), want)


@pytest.mark.parametrize("tag,stamp", [
    (0, (1 << 17) - 1),           # stamp's top bit is bit 32: the high half
    (1, 0),                       # tag bit 0 is bit 33
    ((1 << 30) + 5, 0x10000),     # tag's top bit is the sign bit
    ((1 << 31) - 1, (1 << 17) - 1)])
def test_fields_across_the_halves(tag, stamp):
    """A word whose stamp or tag crosses bit 32 survives a set and a
    gather with every field intact."""
    T = 8
    words = np.zeros((A, T * DSETS), np.int64)
    word = np.asarray(dword_pack(jnp.int64(tag), jnp.int64(stamp), 3, 5))
    got = _set(words, np.array([True]), np.array([9], np.int32),
               np.array([1], np.int32), word[None])
    assert got[1, 9] == word and np.count_nonzero(got) == 1
    row = dir_gather(_store(got), jnp.asarray([9], jnp.int32), A)[0, 1]
    assert int(row) == int(word)
    assert int(dword_tag(row)) == int(dword_tag(jnp.asarray(word)))
    assert int(dword_stamp(row)) == stamp
    assert int(row & 7) == 3


@pytest.mark.parametrize("T", [8, 64])
def test_live_entries_count_states(T):
    """The statistics ring's live count: entries whose state field is not
    I, whatever their other bits hold."""
    rng = np.random.default_rng(T + 3)
    words = _words(rng, T)
    words[:, ::3] &= ~np.int64(7)                       # some I entries
    assert int(dir_live_entries(_store(words))) \
        == np.count_nonzero(words & 7)


def test_make_state_store_is_flat():
    cfg = load_config()
    cfg.set("general/total_cores", 16)
    params = SimParams.from_config(cfg)
    st = make_state(params, has_capi=False)
    d = params.directory
    assert st.dir_word.dtype == jnp.uint32
    assert st.dir_word.shape == (16 * d.num_sets * d.associativity * 2,)
    assert not np.asarray(st.dir_word).any()
