"""Bytes a read NEEDS from device memory: the yardstick behind
``hbm_roof_pct``.  Operands only, each once, in the smaller of their
roaring and their dense form -- no padding, no pool amplification, no
intermediate, no output.  What the program actually moves is its own
affair; this is the floor a perfect engine could not go under, so the
share of the roof it gives can only be low, never above 100%.

``meta`` describes the index (``Dataset``-independent, so the tests
can hand-work it):

    row_bytes    {field: [bytes of row r over all shards]}
    int_bytes    {int field: bytes of all the bit planes a range or an
                  aggregate reads, each plane in its smaller form}
"""

from __future__ import annotations

from perfbench.oracle import leaves


def _leaf_bytes(meta: dict, leaf) -> int:
    if leaf[0] == "row":
        return int(meta["row_bytes"][leaf[1]][leaf[2]])
    return int(meta["int_bytes"][leaf[1]])


def read_bytes(meta: dict, q) -> int:
    """Operand bytes of one call (see ``perfbench.oracle`` for the
    forms)."""
    kind = q[0]
    total = sum(_leaf_bytes(meta, leaf) for leaf in leaves(q))
    if kind == "topn":
        total += int(sum(meta["row_bytes"][q[1]]))
    elif kind in ("sum", "min", "max"):
        total += int(meta["int_bytes"][q[1]])
    elif kind == "groupby":
        total += sum(int(sum(meta["row_bytes"][f])) for f in q[1])
    elif kind != "count":
        raise ValueError(f"unknown call {kind!r}")
    return total


def roof_share_pct(total_bytes: float, busy_s: float, peak_gbps: float
                   ) -> float:
    """Share of the memory roof: needed bytes over busy time over the
    peak.  Over 100% means the bytes are counted too high or the time
    leaves out work: an error, never a number to print."""
    share = total_bytes / busy_s / (peak_gbps * 1e9) * 100.0
    if share > 100.0:
        raise ValueError(
            f"roofline share {share:.1f}% is over 100%: {total_bytes} "
            f"bytes in {busy_s} s busy against {peak_gbps} GB/s")
    return share
