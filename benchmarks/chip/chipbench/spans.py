"""The service's own spans (`repro.obs.trace`), as the per-layer readers
need them.

One request's trace holds its ``submit``, ``plan``, ``result`` spans and a
copy of each shared flush phase (``coalesce``, ``pad``, ``dispatch``,
``execute``, ``demux``): the tracer opens one span per request for a phase
that serves many. Times are `time.monotonic()` seconds, the clock the
benchmark's clients read too.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional


def end(span: dict) -> float:
    return span["start_s"] + span["duration_ms"] / 1000.0


def first(trace: dict, name: str) -> Optional[dict]:
    return next((s for s in trace["spans"]
                 if s["name"] == name and s["duration_ms"] is not None),
                None)


def last(trace: dict, name: str) -> Optional[dict]:
    found = [s for s in trace["spans"]
             if s["name"] == name and s["duration_ms"] is not None]
    return found[-1] if found else None


def unique(traces: Iterable[dict], name: str) -> List[dict]:
    """One span per shared phase: the copies a flush opened for each of
    its requests start at one instant."""
    seen: Dict[float, dict] = {}
    for trace in traces:
        for s in trace["spans"]:
            if s["name"] == name and s["duration_ms"] is not None:
                seen.setdefault(s["start_s"], s)
    return sorted(seen.values(), key=lambda s: s["start_s"])


def children(traces: Iterable[dict], parent: dict, name: str) -> List[dict]:
    for trace in traces:
        if any(s["span_id"] == parent["span_id"] for s in trace["spans"]):
            return [s for s in trace["spans"] if s["name"] == name
                    and s["parent_id"] == parent["span_id"]]
    return []
