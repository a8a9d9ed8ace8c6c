"""Median over the window's requests of the time the HTTP tier took to
answer a result: its `encode` span, which covers turning the result into
JSON and writing the response (program spans). None where no request has
an `encode` span."""
import statistics

from chipbench import spans


def read(r):
    times = [s["duration_ms"] for s in (spans.last(t, "encode")
                                        for t in r.spans) if s]
    return statistics.median(times) if times else None
