"""Seeded SPLASH-2 event traces, generated from a traffic file.

Copies of ``graphite_tpu/events/synth.py``'s ``gen_radix`` and
``gen_fft(writeback=True)``, built per tile with numpy and kept with the
benchmark so that later PRs cannot change the yardstick.  Each returns the
[T, N] event arrays of graphite's trace format (op, addr, arg, arg2),
padded with NOP.  A traffic file names its generator, its parameters, and
overrides of them at a tile count (``scale``).

``radix`` and ``fft`` are built in.  Any other generator is a file of its
own, ``benchmarks/generators/<generator>.py``, which a later PR adds
without editing this one.  It exposes ``generate(tiles, seed, params) ->
{"ops", "addr", "arg", "arg2"}``, ``params`` being ``resolve()``'s output,
and may import this module (the harness directory is on ``sys.path``)
for ``_groups``, ``_one``, ``_pack`` and the address bases.
"""

from __future__ import annotations

import os

import numpy as np

import plugins

GENERATOR_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "generators")

PRIVATE_BASE = 0x1000_0000
PRIVATE_SPAN = 0x0100_0000
SHARED_BASE = 0x8000_0000
PC = 0x400000
COMPUTE, MEM_READ, MEM_WRITE, DONE, BARRIER = 1, 2, 3, 12, 13


def _groups(n, spec):
    """n repetitions of a group of events: spec holds one (op, addr, arg,
    arg2) per event of the group, each a scalar or an [n] array."""
    out = np.zeros((4, n, len(spec)), np.int64)
    for j, fields in enumerate(spec):
        for f, v in enumerate(fields):
            out[f, :, j] = v
    return out.reshape(4, -1)


def _one(op, addr=0, arg=0, arg2=0):
    return np.array([[op], [addr], [arg], [arg2]], np.int64)


def _pack(streams):
    T = len(streams)
    N = max(s.shape[1] for s in streams)
    ev = np.zeros((4, T, N), np.int64)
    for t, s in enumerate(streams):
        ev[:, t, :s.shape[1]] = s
    return {"ops": ev[0].astype(np.int32), "addr": ev[1],
            "arg": ev[2].astype(np.int32), "arg2": ev[3].astype(np.int32)}


KEYS_SEED = 0      # radix.C draws its keys from a fixed seed too


def gen_radix(tiles: int, seed: int, keys_per_tile: int, radix: int) -> dict:
    """One digit pass of SPLASH-2 radix sort.  The keys are one uniform
    draw over [0, radix) from a fixed seed; ``seed`` shuffles each tile's
    keys, so every seed sorts the same keys per tile in another order:
    the same work, other arrivals."""
    base = np.random.default_rng(KEYS_SEED).integers(
        0, radix, size=(tiles, keys_per_tile))
    keys = np.random.default_rng(seed).permuted(base, axis=1)
    return radix_trace(keys, radix)


def radix_trace(keys, radix: int, line_size: int = 64) -> dict:
    """events/synth.py's gen_radix over given [tiles, keys_per_tile] keys:
    local histogram, barrier, binary-tree prefix over the per-tile
    histograms, barrier, rank reads down the tree, permutation writes,
    barrier.  The event count per tile does not depend on the keys."""
    tiles, kpt = keys.shape
    flat = keys.reshape(-1)
    order = np.argsort(flat, kind="stable")
    rank = np.empty_like(order)
    rank[order] = np.arange(flat.size)
    rank = rank.reshape(tiles, kpt)
    hist = SHARED_BASE
    out = SHARED_BASE + 0x400_0000
    tree = SHARED_BASE + 0x200_0000
    stride = max(1, line_size // 8)
    levels = max(1, (tiles - 1).bit_length())
    i = np.arange(kpt)
    d = np.arange(0, radix, stride)
    streams = []
    for t in range(tiles):
        base = PRIVATE_BASE + t * PRIVATE_SPAN
        parts = [_groups(kpt, [(COMPUTE, PC, 4, 4),
                               (MEM_READ, base + i * 8, 8, 0),
                               (MEM_WRITE, hist + (t * radix + keys[t]) * 8,
                                8, 0)]),
                 _one(BARRIER, 0, 0, tiles)]
        node_base, width = 0, tiles
        for lvl in range(levels):
            if t % (1 << (lvl + 1)) == 0 and width > 1:
                sib = node_base + (t >> lvl) + 1
                parent = node_base + width + (t >> (lvl + 1))
                parts.append(_groups(d.size, [
                    (COMPUTE, PC, 2, 2),
                    (MEM_READ, tree + (sib * radix + d) * 8, 8, 0),
                    (MEM_WRITE, tree + (parent * radix + d) * 8, 8, 0)]))
            node_base += width
            width = max(1, width // 2)
        parts.append(_one(BARRIER, 0, 1, tiles))
        nodes, node_base, width = [], 0, tiles
        for lvl in range(levels):
            nodes.append(node_base + (t >> lvl))
            node_base += width
            width = max(1, width // 2)
        nodes = np.asarray(nodes)
        parts.append(_groups(levels, [(COMPUTE, PC, 2, 2),
                                      (MEM_READ, tree + nodes * radix * 8,
                                       8, 0)]))
        parts.append(_groups(kpt, [(COMPUTE, PC, 6, 6),
                                   (MEM_READ, base + i * 8, 8, 0),
                                   (MEM_WRITE, out + rank[t] * 8, 8, 0)]))
        parts += [_one(BARRIER, 0, 2, tiles), _one(DONE)]
        streams.append(np.concatenate(parts, axis=1))
    return _pack(streams)


def gen_fft(tiles: int, seed: int, points_per_tile: int) -> dict:
    """SPLASH-2 six-step FFT with fft.C's ping-pong transposes: transpose,
    local FFTs, transpose back, local FFTs, transpose.  The transposes are
    all-to-all: each tile reads a block of every partition.  ``seed``
    permutes which tile owns which partition; the shape stays fixed."""
    perm = np.random.default_rng(seed).permutation(tiles)
    ppt = points_per_tile
    elem = 16
    part = ppt * elem
    src = SHARED_BASE
    dst = SHARED_BASE + 0x1000_0000
    blk = max(1, ppt // max(1, tiles))
    log_n = max(1, (ppt * tiles).bit_length() - 1)
    q = np.repeat(np.arange(tiles), blk)
    ii = np.tile(np.arange(blk), tiles)
    pts = np.arange(ppt)
    streams = []
    for t in range(tiles):
        p = int(perm[t])

        def transpose(phase, a_from, a_to):
            return [_groups(q.size, [
                (COMPUTE, PC, 2, 2),
                (MEM_READ, a_from + q * part + (p * blk + ii) * elem, elem, 0),
                (MEM_WRITE, a_to + p * part + (q * blk + ii) * elem, elem,
                 0)]),
                _one(BARRIER, 0, phase, tiles)]

        def local(phase, base):
            a = base + p * part + pts * elem
            return [_groups(ppt, [(COMPUTE, PC, 5 * log_n, 5 * log_n),
                                  (MEM_READ, a, elem, 0),
                                  (MEM_WRITE, a, elem, 0)]),
                    _one(BARRIER, 0, phase, tiles)]

        parts = transpose(0, src, dst) + local(1, dst) \
            + transpose(2, dst, src) + local(3, src) \
            + transpose(4, src, dst) + [_one(DONE)]
        streams.append(np.concatenate(parts, axis=1))
    return _pack(streams)


BUILT_IN = ("radix", "fft")


def generators() -> set:
    """Every generator a traffic file may name: the built-in ones and the
    files of ``GENERATOR_DIR``."""
    return set(BUILT_IN) | plugins.names(GENERATOR_DIR)


def resolve(traffic: dict, tiles: int) -> dict:
    """The traffic file's settings at ``tiles`` tiles: its ``params`` with
    the ``scale`` entry for that tile count laid over them."""
    out = dict(traffic.get("params", {}))
    out.update(traffic.get("scale", {}).get(str(tiles), {}))
    out.setdefault("window", traffic.get("window", "restart"))
    return out


def generate(traffic: dict, tiles: int, seed: int) -> dict:
    """Event arrays of this traffic at ``tiles`` tiles, from ``seed``."""
    p = resolve(traffic, tiles)
    gen = traffic["generator"]
    if gen == "radix":
        kpt = p.get("keys_per_tile") or p["total_keys"] // tiles
        return gen_radix(tiles, seed, kpt, p["radix"])
    if gen == "fft":
        ppt = p.get("points_per_tile") or (1 << p["points_log2"]) // tiles
        return gen_fft(tiles, seed, ppt)
    return plugins.load(GENERATOR_DIR, gen, "generator").generate(
        tiles, seed, p)
