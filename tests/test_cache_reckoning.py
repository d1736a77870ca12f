"""What share of ``seg-dense``'s reads the result cache can answer,
reckoned from the generator alone (ISSUE 42).

No server and no chip: ``perfbench/mix.py`` gives the warm-up stream and
the window of a seed, and a read is a hit when its key was seen earlier
in the warm-up or the window.  With the key as the query wrote it the
share is the 28.86% that the server's own counter read at this seed
(``PERF.md`` section 5); with the operands of and / or / xor, and the
tail of andnot, in one order it is 41.14%.  A later change to the
generator that empties the mechanism (operands drawn in one order, say)
moves these numbers without a chip run, and so does a change to
the tree's one walk (``parallel/prepared.py``: its ``sig``) that stops
telling the two apart or starts
telling more apart.  Counts of keys, not device numbers."""

from __future__ import annotations

import pytest

from perfbench import mix, oracle
from pilosa_tpu.models.holder import Holder
from pilosa_tpu.parallel.executor import Executor
from pilosa_tpu.pql import parse

N_ROWS = {"demo": 64}  # perfbench/configs/segmentation-134m.json
SECONDS = 51           # BENCHMARK.json run_seconds
#: seed -> (hit share with the key as written, with operands sorted,
#: distinct sorted keys in the window), two decimals
RECKONED = {
    3000003721: (28.86, 41.14, 3349),
    3000003901: (28.84, 40.91, 3355),
    7: (28.80, 40.44, 3365),
}


def _written(b) -> tuple:
    return tuple(_written(x) if isinstance(x, list) else x for x in b)


def by_rule(b) -> tuple:
    """An oracle-form tree with the rule applied by hand."""
    if b[0] == "row":
        return tuple(b)
    kids = [by_rule(x) for x in b[1:]]
    keep = 1 if b[0] == "andnot" else 0
    return (b[0], *kids[:keep], *sorted(kids[keep:], key=repr))


def _streams(seed: int) -> tuple[list, list]:
    """(the warm-up's calls, the window's calls in the order sent)."""
    traffic = mix.load_traffic("seg-dense")
    warm = mix.family(traffic).generate(
        traffic["params"], N_ROWS, mix._draw(seed, 1),
        traffic["warmup_requests"])
    window = mix.build(traffic, N_ROWS, seed, SECONDS)
    return warm, [window.queries[i] for i in window.order]


def _hit_share(warm, window, key) -> tuple[float, int]:
    seen = {key(q) for q in warm}
    hits, distinct = 0, set()
    for q in window:
        k = key(q)
        hits += k in seen
        seen.add(k)
        distinct.add(k)
    return round(100 * hits / len(window), 2), len(distinct)


@pytest.mark.parametrize("seed", list(RECKONED))
def test_hit_share_by_the_generator_alone(seed):
    warm, window = _streams(seed)
    assert (len(warm), len(window)) == (1600, 4896)
    written, ordered, n_keys = RECKONED[seed]
    assert _hit_share(warm, window, lambda q: _written(q[1]))[0] == written
    assert _hit_share(warm, window, lambda q: by_rule(q[1])) == (ordered,
                                                                n_keys)


def test_the_programs_key_gives_the_reckoned_share(tmp_path):
    """The same stream through the program's own key, the ``sig`` the
    tree's one walk leaves (an empty ``demo`` field: a signature reads
    no data)."""
    holder = Holder(str(tmp_path / "h"))
    idx = holder.create_index("i")
    idx.create_field("demo")
    ex = Executor(holder)

    def key(q):
        tree = parse(oracle.pql(q)).calls[0].children[0]
        return ex._prepare(idx, tree).sig

    seed = 3000003721
    warm, window = _streams(seed)
    assert _hit_share(warm, window, key) == RECKONED[seed][1:]
    holder.close()
