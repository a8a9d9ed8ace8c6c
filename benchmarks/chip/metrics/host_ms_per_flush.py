"""Host time of the scheduler per flush (program spans): from a flush's
`coalesce` start to its `demux` end, less the `dispatch` spans in between
(planning, padding, demultiplexing and the host work between groups)."""
from chipbench import spans


def read(r):
    demuxes = spans.unique(r.spans, "demux")
    dispatches = spans.unique(r.spans, "dispatch")
    per_flush = []
    for c in spans.unique(r.spans, "coalesce"):
        after = [d for d in demuxes if d["start_s"] >= c["start_s"]]
        if not after:
            continue
        stop = spans.end(after[0])
        busy = sum(d["duration_ms"] for d in dispatches
                   if c["start_s"] <= d["start_s"] < stop)
        per_flush.append((stop - c["start_s"]) * 1000.0 - busy)
    return sum(per_flush) / len(per_flush) if per_flush else None
