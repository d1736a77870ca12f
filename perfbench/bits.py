"""Bit-level helpers shared by the generators, the loader and the
oracle.  Everything here is numpy on the host; nothing imports the
program or JAX.

A row over S shards is kept as packed little-endian ``uint64`` words,
``S * WORDS_PER_SHARD`` of them: bit ``c`` of the row is bit ``c & 63``
of word ``c >> 6``.  That is also the byte order of a roaring bitmap
container, so a row's words reshape to ``[S * 16, 1024]`` containers
without a copy."""

from __future__ import annotations

import numpy as np

SHARD_EXP = 20  # the source's own shard width; never cut
SHARD_WIDTH = 1 << SHARD_EXP
WORDS_PER_SHARD = SHARD_WIDTH // 64
CONTAINER_BITS = 1 << 16
CONTAINER_WORDS = CONTAINER_BITS // 64
CONTAINERS_PER_SHARD = SHARD_WIDTH // CONTAINER_BITS


def pack_bool(mask: np.ndarray) -> np.ndarray:
    """bool[n] (n a multiple of 64) -> packed uint64 words."""
    return np.packbits(mask, bitorder="little").view(np.uint64)


def pack_positions(cols: np.ndarray, n_words: int) -> np.ndarray:
    """Sorted-or-not column ids -> packed uint64 words."""
    words = np.zeros(n_words, dtype=np.uint64)
    cols = np.asarray(cols, dtype=np.uint64)
    np.bitwise_or.at(words, (cols >> np.uint64(6)).astype(np.int64),
                     np.uint64(1) << (cols & np.uint64(63)))
    return words


def unpack_bool(words: np.ndarray) -> np.ndarray:
    """Packed uint64 words -> bool[64 * n]."""
    return np.unpackbits(words.view(np.uint8),
                         bitorder="little").view(np.bool_)


def count(words: np.ndarray) -> int:
    return int(np.bitwise_count(words).sum(dtype=np.uint64))
