"""Pilosa's 64-bit roaring wire format (cookie 12348), written by the
benchmark itself so that the load path does not depend on the program's
serializer.  Layout (docs/architecture.md of the reference; the
program's ``storage/roaring.py`` decodes the same):

    u16 cookie=12348, u8 version=0, u8 flags, u32 n
    n x (u64 key, u16 type, u16 cardinality-1)     type 1 array, 2 bitmap
    n x u32 offset of the container's payload from the start
    payloads: array = card x u16 sorted low bits; bitmap = 1024 x u64

Only array and bitmap containers are written (array up to 4096 bits,
bitmap above): what kind the *server* keeps a container in is its own
serializer's rule, applied to the bits, not to the wire type."""

from __future__ import annotations

import numpy as np

from perfbench.bits import CONTAINER_WORDS

COOKIE = 12348
ARRAY_MAX_CARD = 4096
_DESC = np.dtype([("key", "<u8"), ("type", "<u2"), ("card", "<u2")])


def _assemble(keys, types, cards, sizes, payload: bytes) -> bytes:
    n = len(keys)
    head = np.zeros(1, dtype=np.dtype([("cookie", "<u2"), ("ver", "u1"),
                                       ("flags", "u1"), ("n", "<u4")]))
    head["cookie"], head["n"] = COOKIE, n
    desc = np.empty(n, dtype=_DESC)
    desc["key"], desc["type"], desc["card"] = keys, types, cards - 1
    offs = 8 + 16 * n + np.concatenate(([0], np.cumsum(sizes)[:-1]))
    if n and int(offs[-1]) + int(sizes[-1]) > 0xFFFFFFFF:
        raise ValueError("roaring payload over 4 GiB")
    return b"".join((head.tobytes(), desc.tobytes(),
                     offs.astype("<u4").tobytes(), payload))


def encode_bitmaps(keys: np.ndarray, words: np.ndarray) -> bytes:
    """Dense containers: keys u64[n] ascending, words u64[n, 1024].
    Empty containers are dropped; every other one goes as a bitmap."""
    words = words.reshape(-1, CONTAINER_WORDS)
    cards = np.bitwise_count(words).sum(axis=1, dtype=np.int64)
    keep = cards > 0
    if not keep.all():
        keys, words, cards = keys[keep], words[keep], cards[keep]
    n = len(keys)
    return _assemble(keys, np.full(n, 2), cards, np.full(n, 8192),
                     np.ascontiguousarray(words).tobytes())


def encode_positions(pos: np.ndarray) -> bytes:
    """Sorted unique positions (fragment space: row * 2^20 + offset) ->
    roaring bytes; each container an array, or a bitmap above 4096."""
    pos = np.asarray(pos, dtype=np.uint64)
    if len(pos) == 0:
        return b""
    ckey = pos >> np.uint64(16)
    starts = np.flatnonzero(np.concatenate(([True], ckey[1:] != ckey[:-1])))
    keys = ckey[starts]
    cards = np.diff(np.concatenate((starts, [len(pos)])))
    low = (pos & np.uint64(0xFFFF)).astype(np.int64)
    big = cards > ARRAY_MAX_CARD
    sizes = np.where(big, 8192, 2 * cards)
    # the payload as u16 cells: an array's cells are its sorted low
    # bits, a bitmap's 4096 cells its packed membership
    cell0 = np.concatenate(([0], np.cumsum(sizes)[:-1])) // 2
    out = np.zeros(int(sizes.sum()) // 2, dtype="<u2")
    cont = np.repeat(np.arange(len(keys)), cards)
    rank = np.arange(len(pos)) - np.repeat(starts, cards)
    in_big = big[cont]
    small = ~in_big
    out[cell0[cont[small]] + rank[small]] = low[small]
    if in_big.any():
        which = np.flatnonzero(big)
        slot = np.cumsum(big) - 1  # container -> row of ``member``
        member = np.zeros(len(which) << 16, dtype=np.bool_)
        member[(slot[cont[in_big]] << 16) + low[in_big]] = True
        packed = np.packbits(member, bitorder="little").view("<u2") \
            .reshape(len(which), 4096)
        for row, c0 in zip(packed, cell0[which].tolist()):
            out[c0:c0 + 4096] = row
    return _assemble(keys, np.where(big, 2, 1), cards, sizes, out.tobytes())
