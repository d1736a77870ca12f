"""What one measured window leaves behind for the per-layer readers.

A reader is a module under ``perfbench/readers/`` with one function,
``read(cap) -> float | None``; it returns ``None`` when the run gave it
nothing to read, and the harness then leaves the metric out."""

from __future__ import annotations

import dataclasses

from perfbench.loadgen import Record


@dataclasses.dataclass
class Capture:
    records: list[Record]        # the window's requests, by due time
    queries: list                # the run's calls (oracle forms)
    meta: dict                   # bytes_needed's description of the index
    devices_before: dict         # GET /debug/devices at window start
    devices_after: dict          # ... and after its last answer
    device_kind: str
    peaks: dict                  # perfbench/peaks.json
    trace: dict | None = None    # trace_reduce's summary
    trace_span: tuple[float, float] | None = None  # in window time

    def profiled(self) -> list[Record]:
        return [r for r in self.records if r.status == 200 and r.profile]

    def launched(self) -> list[Record]:
        """Reads the server did not answer from its result cache."""
        return [r for r in self.profiled() if not r.profile.get("cached")]

    def launched_in_trace(self) -> list[Record]:
        if self.trace_span is None:
            return []
        lo, hi = self.trace_span
        return [r for r in self.launched() if lo <= r.sent < hi]
