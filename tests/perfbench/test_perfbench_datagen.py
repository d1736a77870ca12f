"""The generators: a pure function of (configuration, seed), whose
import bodies hold exactly the bits the oracle holds -- checked with the
PROGRAM's roaring decoder, so a change to the wire format the server
reads is caught here, on a CPU."""

import numpy as np
import pytest

from perfbench import wire
from perfbench.bits import (SHARD_WIDTH, WORDS_PER_SHARD, count,
                            pack_positions, unpack_bool)
from perfbench.datagen import common

from test_perfbench_oracle import rehearsal_dataset

CONFIGS = ["segmentation-134m", "segmentation-traits", "taxi-rehearse"]


@pytest.mark.parametrize("config", CONFIGS)
def test_same_seed_same_data_other_seed_other_data(config, monkeypatch):
    _, a = rehearsal_dataset(config, seed=3_000_000_007)
    monkeypatch.setattr(common, "gen_threads", lambda: 1)
    _, b = rehearsal_dataset(config, seed=3_000_000_007)
    _, c = rehearsal_dataset(config, seed=3_000_000_008)
    assert a.payloads == b.payloads  # whatever the number of threads
    assert [p[:2] for p in a.payloads] == [p[:2] for p in c.payloads]
    assert all(x[2] != y[2] for x, y in zip(a.payloads, c.payloads))
    for f in a.values:
        assert (a.values[f][0] == b.values[f][0]).all()
        assert (a.values[f][1] == b.values[f][1]).all()


@pytest.mark.parametrize("config", CONFIGS)
def test_import_bodies_hold_the_oracles_bits(config):
    from pilosa_tpu.storage import roaring

    _, ds = rehearsal_dataset(config)
    seen = {}
    for field, shard, blob in ds.payloads:
        pos = np.sort(roaring.decode_positions(blob)).astype(np.uint64)
        rows = pos >> np.uint64(20)
        for r in np.unique(rows).tolist():
            cols = (pos[rows == r] & np.uint64(SHARD_WIDTH - 1)) \
                + np.uint64(shard * SHARD_WIDTH)
            seen.setdefault((field, r), []).append(cols)
    assert {f for f, _ in seen} == set(ds.n_rows)
    for (field, r), parts in seen.items():
        assert r < ds.n_rows[field]
        want = ds.row(field, r)
        got = pack_positions(np.concatenate(parts), ds.n_words)
        assert (got == want).all(), (field, r)


def test_segmentation_rows_have_their_styles_and_fills():
    from perfbench.datagen import segmentation

    cfg, ds = rehearsal_dataset("segmentation-traits")
    from pilosa_tpu.storage.roaring import (KIND_ARRAY, KIND_BITMAP,
                                            KIND_RUN, container_stats,
                                            pick_kind)
    fills = segmentation.demo_fills(cfg)
    for r, fill in enumerate(fills):
        got = count(ds.row("demo", r)) / ds.n_cols
        assert abs(got - fill) < 0.01, (r, got, fill)
    # the program's own serializer rule sorts the three styles into the
    # three kinds
    want = {"array": KIND_ARRAY, "run": KIND_RUN, "bitmap": KIND_BITMAP}
    for r in range(ds.n_rows["trait"]):
        words = ds.row("trait", r)[:WORDS_PER_SHARD].reshape(16, 1024)
        kinds = {pick_kind(*container_stats(w)) for w in words if w.any()}
        assert kinds == {want[segmentation.trait_style(cfg, r)]}, r


def test_taxi_fields_have_one_row_a_ride_and_values_on_their_share():
    cfg, ds = rehearsal_dataset("taxi-rehearse")
    for field, codes in ds.codes.items():
        assert codes.max() < ds.n_rows[field]
        total = sum(count(ds.row(field, r)) for r in range(ds.n_rows[field]))
        assert total == ds.n_cols
    for field, (cols, vals) in ds.values.items():
        spec = cfg["fields"][field]
        assert len(cols) == round(ds.n_cols * cfg["value_share"])
        assert (np.diff(cols) > 0).all()
        assert vals.min() >= spec["min"] and vals.max() <= spec["max"]
        assert len(np.unique(vals)) > 100


@pytest.mark.parametrize("n_big", [0, 1, 3])
def test_wire_encoder_round_trips_through_the_programs_decoder(n_big):
    from pilosa_tpu.storage import roaring

    rng = np.random.default_rng(n_big)
    parts = [rng.integers(0, 1 << 26, size=4000).astype(np.uint64)]
    for k in range(n_big):
        parts.append(np.uint64((7 + 5 * k) << 16) + rng.choice(
            1 << 16, size=5000 + 20000 * k, replace=False).astype(np.uint64))
    pos = np.unique(np.concatenate(parts))
    assert (np.sort(roaring.decode_positions(wire.encode_positions(pos)))
            == pos).all()
    words = rng.integers(0, 1 << 63, size=(6, 1024), dtype=np.uint64)
    words[2] = 0  # an empty container is dropped
    keys = np.array([0, 3, 4, 9, 16, 17], dtype=np.uint64)
    k2, w2, _ = roaring.decode(wire.encode_bitmaps(keys, words))
    keep = [0, 1, 3, 4, 5]
    assert (k2 == keys[keep]).all() and (w2 == words[keep]).all()
    assert wire.encode_positions(np.array([], dtype=np.uint64)) == b""


def test_unpack_is_the_inverse_of_pack():
    cols = np.array([0, 63, 64, 1 << 20, (1 << 21) - 1], dtype=np.uint64)
    words = pack_positions(cols, 2 * WORDS_PER_SHARD)
    assert np.flatnonzero(unpack_bool(words)).tolist() == cols.tolist()
    assert count(words) == len(cols)


def test_demo_rows_keep_issue_23s_fills_and_the_programs_kinds():
    """16 rows at 25-70% fill and 48 at 2-20%, hottest first; under the
    program's own serializer rule a row under 6.25% is array containers
    and one over it bitmap containers."""
    from perfbench.datagen import segmentation
    from pilosa_tpu.storage.roaring import (KIND_ARRAY, KIND_BITMAP,
                                            container_stats, pick_kind)
    from test_perfbench_oracle import load_config

    full = segmentation.demo_fills(load_config("segmentation-134m"))
    assert len(full) == 64 and (np.diff(full) < 0).all()
    assert ((full[:16] > 0.25) & (full[:16] < 0.70)).all()
    assert ((full[16:] > 0.02) & (full[16:] < 0.20)).all()
    cfg, ds = rehearsal_dataset("segmentation-134m")
    fills = segmentation.demo_fills(cfg)
    assert (fills < 0.25).sum() == 9 and fills.min() < 0.05
    for r, fill in enumerate(fills):
        if abs(fill - 0.0625) < 0.005:
            continue  # on the edge: containers of both kinds
        words = ds.row("demo", r)[:WORDS_PER_SHARD].reshape(16, 1024)
        kinds = {pick_kind(*container_stats(w)) for w in words}
        assert kinds == {KIND_ARRAY if fill < 0.0625 else KIND_BITMAP}, r
