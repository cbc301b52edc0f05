"""What a window leaves behind, as the per-layer metrics' readers see it."""

from __future__ import annotations

import contextlib
import dataclasses
import statistics
from typing import Dict, List, Optional


@dataclasses.dataclass
class Request:
    query: str  # the query's name in the traffic file
    client: int
    sent_s: float  # host clock, just before the request is sent
    done_s: float  # host clock, when its whole answer has been read
    status: int
    body: object  # the decoded answer
    metrics: object  # the system's counters for this request, or None

    @property
    def wall_ms(self) -> float:
        return (self.done_s - self.sent_s) * 1e3


@dataclasses.dataclass
class Window:
    requests: List[Request]
    queries: Dict[str, dict]  # the traffic file's queries by name
    column_bytes: Dict[str, int]  # resident bytes per row of each column
    traced: List[Request] = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None  # trace_reduce.reduce_trace's result
    peaks: Optional[dict] = None  # peaks.json's entry for this device kind

    def field(self, name: str) -> List[float]:
        """One QueryMetrics field over every request that has metrics."""
        return [
            getattr(r.metrics, name) for r in self.requests
            if r.metrics is not None
        ]


STATS = {
    "median": statistics.median,
    "mean": statistics.fmean,
}


def field_stat(window: Window, reader: dict) -> Optional[float]:
    """A metric whose file needs no code: `{"stat": ..., "field": ...}`
    over the window's requests.  None where there is nothing to read."""
    values = window.field(reader["field"])
    return float(STATS[reader["stat"]](values)) if values else None


class Hooks:
    """What a loop calls around passes and requests.  The untraced run
    uses this base: every hook does nothing."""

    traced: List[Request] = []  # the requests a profiler saw: none here

    def pass_begins(self, index: int) -> None:
        pass

    def pass_ended(self, index: int, requests: List[Request]) -> None:
        pass

    def request(self, query_name: str):
        return contextlib.nullcontext()

    def stop(self) -> None:
        pass
