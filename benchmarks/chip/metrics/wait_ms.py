"""Median over the window's requests of the time a request waited in the
queue for the flush daemon: its `coalesce` start less its `submit` end
(program spans)."""
import statistics

from chipbench import spans


def read(r):
    waits = []
    for trace in r.spans:
        sub, co = spans.first(trace, "submit"), spans.first(trace, "coalesce")
        if sub and co:
            waits.append((co["start_s"] - spans.end(sub)) * 1000.0)
    return statistics.median(waits) if waits else None
