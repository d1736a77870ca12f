"""Global WAL fd budget (runtime/filebudget.py) — the reference's
syswrap file-count cap (syswrap/os.go:41): past the cap, LRU fds close
behind the scenes and reopen transparently on the next append, so a
10B-scale holder (~9.5k fragments) cannot blow ``ulimit -n``.

Tiers: handle/LRU unit behavior, fragment WAL durability across
evictions and snapshots, and a subprocess that opens far more
fragments than a LOWERED ``RLIMIT_NOFILE`` allows (the VERDICT #4
acceptance shape)."""

from __future__ import annotations

import os
import subprocess
import sys

import pytest

from pilosa_tpu.models.fragment import Fragment
from pilosa_tpu.runtime import filebudget


@pytest.fixture
def budget():
    """A private budget instance patched in as the module global, so
    the cap changes here never leak into other tests."""
    old = filebudget._budget
    b = filebudget.FileBudget(4)
    filebudget._budget = b
    yield b
    filebudget._budget = old


class TestBudgetUnit:
    def test_lru_eviction_and_reopen(self, budget, tmp_path):
        handles = [filebudget.open_append(str(tmp_path / f"w{i}"))
                   for i in range(10)]
        assert budget.open_count() <= 4
        assert budget.evictions >= 6
        for rnd in range(3):
            for i, h in enumerate(handles):
                h.write(f"{rnd}:{i};".encode())
                assert budget.open_count() <= 4
        assert budget.reopens > 0
        for h in handles:
            h.close()
        assert budget.open_count() == 0
        for i in range(10):
            data = (tmp_path / f"w{i}").read_bytes()
            assert data == f"0:{i};1:{i};2:{i};".encode(), i

    def test_truncate_only_on_first_open(self, budget, tmp_path):
        p = str(tmp_path / "t")
        h = filebudget.open_append(p, truncate=True)
        h.write(b"abc")
        # force eviction of h, then write again: must APPEND, not
        # re-truncate
        extra = [filebudget.open_append(str(tmp_path / f"x{i}"))
                 for i in range(4)]
        h.write(b"def")
        h.close()
        for e in extra:
            e.close()
        assert (tmp_path / "t").read_bytes() == b"abcdef"

    def test_write_after_close_fails_loudly(self, budget, tmp_path):
        h = filebudget.open_append(str(tmp_path / "c"))
        h.close()
        with pytest.raises(ValueError, match="closed"):
            h.write(b"x")

    def test_rename_to_follows_evicted_handle(self, budget, tmp_path):
        h = filebudget.open_append(str(tmp_path / "old"), truncate=True)
        h.write(b"one;")
        # evict h, then rename: the reopen after the rename must hit
        # the NEW path (a stale reopen would resurrect "old")
        extra = [filebudget.open_append(str(tmp_path / f"y{i}"))
                 for i in range(4)]
        h.rename_to(str(tmp_path / "new"))
        h.write(b"two;")
        h.close()
        for e in extra:
            e.close()
        assert (tmp_path / "new").read_bytes() == b"one;two;"
        assert not (tmp_path / "old").exists()

    def test_set_cap_shrinks_live(self, budget, tmp_path):
        handles = [filebudget.open_append(str(tmp_path / f"s{i}"))
                   for i in range(4)]
        assert budget.open_count() == 4
        budget.set_cap(2)
        assert budget.open_count() <= 2
        for h in handles:
            h.write(b"z")  # all still writable via reopen
            h.close()

    def test_prometheus_lines(self, budget, tmp_path):
        h = filebudget.open_append(str(tmp_path / "m"))
        text = filebudget.prometheus_lines()
        assert "pilosa_tpu_wal_fd_cap 4" in text
        assert "pilosa_tpu_wal_fd_open 1" in text
        h.close()


class TestFragmentUnderBudget:
    def test_wal_durability_across_evictions(self, budget, tmp_path):
        """More fragments than the cap, interleaved writes; every bit
        must survive a reopen (the WAL append path reopens evicted fds
        transparently)."""
        frags = [Fragment(str(tmp_path / f"f{i}"), "i", "f", "standard", i)
                 for i in range(9)]
        for rnd in range(4):
            for i, fr in enumerate(frags):
                fr.set_bit(rnd, i * fr.width + 17 * i + rnd)
        assert budget.open_count() <= 4
        assert budget.reopens > 0
        for fr in frags:
            fr.close()
        for i in range(9):
            fr = Fragment(str(tmp_path / f"f{i}"), "i", "f", "standard", i)
            for rnd in range(4):
                assert fr.bit(rnd, i * fr.width + 17 * i + rnd), \
                    (i, rnd)
            fr.close()

    def test_snapshot_overflow_rename_with_eviction(self, budget,
                                                    tmp_path):
        """The snapshot's phase-3 overflow-segment commit renames the
        WAL while the budgeted handle may be evicted — acked appends
        must never strand in a resurrected .wal.new."""
        fr = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0)
        for i in range(50):
            fr.set_bit(0, i)
        fr.snapshot()
        # evict the fragment's (post-snapshot) WAL handle
        extra = [filebudget.open_append(str(tmp_path / f"e{i}"))
                 for i in range(4)]
        for i in range(50, 80):
            fr.set_bit(1, i)  # appends via reopen on the RENAMED path
        for e in extra:
            e.close()
        fr.close()
        assert not os.path.exists(str(tmp_path / "frag") + ".wal.new")
        re = Fragment(str(tmp_path / "frag"), "i", "f", "standard", 0)
        assert all(re.bit(0, i) for i in range(50))
        assert all(re.bit(1, i) for i in range(50, 80))
        re.close()


_RLIMIT_SCRIPT = r"""
import os, resource, sys
sys.path.insert(0, sys.argv[1])
os.environ["PILOSA_TPU_MAX_WAL_FILES"] = "64"
soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
resource.setrlimit(resource.RLIMIT_NOFILE, (min(256, hard), hard))

from pilosa_tpu.models.holder import Holder
from pilosa_tpu.runtime import filebudget
from pilosa_tpu.shardwidth import SHARD_WIDTH

d = sys.argv[2]
h = Holder(d)
idx = h.create_index("i")
# 2 fields x 200 shards = 400 fragments, far over both the 64-fd
# budget and what a 256 RLIMIT_NOFILE could hold un-budgeted
for fname in ("a", "b"):
    f = idx.create_field(fname)
    rows = [0] * 200 + [1] * 200
    cols = [s * SHARD_WIDTH + 7 for s in range(200)] * 2
    f.import_bits(rows, cols)
assert filebudget.budget().open_count() <= 64, \
    filebudget.budget().open_count()
assert filebudget.budget().evictions > 0
# every fragment answers, and a second write round still lands
for fname in ("a", "b"):
    f = idx.field(fname)
    for s in range(200):
        f.set_bit(2, s * SHARD_WIDTH + 9)
h.close()

h2 = Holder(d)
idx2 = h2.index("i")
from pilosa_tpu.ops.bitmap import unpack_positions
for fname in ("a", "b"):
    f2 = idx2.field(fname)
    for s in (0, 99, 199):
        assert list(unpack_positions(f2.row(0, s))) == [7], (fname, s)
        assert list(unpack_positions(f2.row(2, s))) == [9], (fname, s)
h2.close()
print("RLIMIT-OK", flush=True)
# skip interpreter teardown: with the lowered RLIMIT still in force,
# native-runtime atexit threads (XLA/BLAS) can die in C++ unwinding
# AFTER everything under test has passed and closed cleanly
os._exit(0)
"""


def test_many_fragments_under_lowered_rlimit(tmp_path):
    """VERDICT #4 acceptance: open far more fragments than the fd cap
    under a lowered RLIMIT_NOFILE; the budget must keep the process
    under the limit with every write durable."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    out = subprocess.run(
        [sys.executable, "-c", _RLIMIT_SCRIPT, repo, str(tmp_path / "h")],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "RLIMIT-OK" in out.stdout
