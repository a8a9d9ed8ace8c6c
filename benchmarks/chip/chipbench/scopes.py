"""The device trace by the program's own names: scopes and program runs.

The epoch cores name their device work with `jax.named_scope`
(``repro.core.asysvrg.SCOPES``); every op compiled inside a scope carries
its name as a component of the op's ``tf_op`` path (`chipbench.xplane`).
Over the traced window this reduces, per chip:

- the device seconds of each scope: every op counts for each scope on its
  path, by its own time, which leaves out the ops nested inside it. A
  loop's ``while`` op spans its whole loop and carries no ``tf_op``; its
  own time (the loop's control between body ops) counts for the scopes
  that every op nested in it shares;
- the inner steps: the calls of the `svrg_update` kernel, one per step at
  any group width;
- the calls of each scope: the kernel calls and the starts of program runs
  cut the ops into segments, and a scope has one call in a segment for
  each place in the program (the path up to the scope) that its ops
  there come from. The compiler interleaves independent work (the
  snapshot pass with the loss at w0, or the loss at w0 with the loss
  after the epoch), so a run of consecutive ops is no call. Ops that
  neither carry a ``tf_op`` nor enclose named ops count for nothing;
- the program runs: the events of the plane's "XLA Modules" line, one per
  run of a program; a run still going when the profiler stops is there
  too, up to the stop.

The scope names are the program's; they are written out here, not
imported, so that this reads a program that names none of them too.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from chipbench import readings, xplane

SNAPSHOT = "snapshot"
INNER_STEP = "inner_step"
READ = "read"
SAMPLE_GRAD = "sample_grad"
DROP_MASK = "drop_mask"
SVRG_UPDATE = "svrg_update"
LOSS = "loss"
SCOPES = (SNAPSHOT, INNER_STEP, READ, "read_consistent", "read_inconsistent",
          "read_unlock", SAMPLE_GRAD, DROP_MASK, SVRG_UPDATE, LOSS)
KERNEL_OP = "pallas_call"        # the kernel's own op inside its scope
MODULES_LINE = "XLA Modules"

_TRANSFORM = re.compile(r"^[\w.]+\((.*)\)$")   # vmap(loss), jit(_uniform)


def path_scopes(tf_op: str) -> Tuple[Dict[str, str], str]:
    """({scope of SCOPES on an op's ``tf_op`` path: the path up to it},
    the op's own name). A path is ``/``-separated; its last component is
    the op's own ``name:type``. A scope opened right inside a transform is
    printed wrapped in it (``vmap(loss)``) and is unwrapped here; a scope
    nested in itself counts once, at its outer place."""
    *parts, own = tf_op.split("/")
    found: Dict[str, str] = {}
    for i, part in enumerate(parts):
        while (m := _TRANSFORM.match(part)):
            part = m.group(1)
        if part in SCOPES and part not in found:
            found[part] = "/".join(parts[:i + 1])
    return found, own.partition(":")[0]


@dataclasses.dataclass
class ScopeTrace:
    window_s: float                  # the traced span
    busy_s: float                    # union of ops, mean over chips
    program_s: float                 # union of program runs, mean over chips
    scope_s: Dict[str, float]        # scope -> device seconds, all chips
    scope_calls: Dict[str, int]      # scope -> calls, all chips
    inner_steps: int                 # svrg_update kernel calls, all chips
    named_ops: int                   # ops in the window with a tf_op
    chips_seen: int = 0


def _union_s(intervals: List[Tuple[int, int]]) -> float:
    return sum(t - s for s, t in readings._union(intervals)) / 1e9


def _nesting(ops: List[Tuple[int, int, str]]):
    """(each op's own time: its span less the spans nested in it; the ops
    enclosing each op). ``ops`` are sorted by start, the longer first
    where two start together."""
    own = [t - s for s, t, _ in ops]
    enclosing: List[Tuple[int, ...]] = []
    open_: List[int] = []
    for k, (s, t, _) in enumerate(ops):
        while open_ and ops[open_[-1]][1] <= s:
            open_.pop()
        if open_ and t <= ops[open_[-1]][1]:
            own[open_[-1]] -= t - s
        enclosing.append(tuple(a for a in open_ if t <= ops[a][1]))
        open_.append(k)
    return own, enclosing


def _op_scopes(ops, meta, enclosing, parsed):
    """Each op's ({scope: place}, whether it is the kernel's call,
    whether it has a ``tf_op``), or None. An op without a ``tf_op`` takes
    the scopes all the named ops nested in it share, at no place: it adds
    time to a call of theirs, never a call."""
    out = []
    for _, _, name in ops:
        tf_op = meta.get(name, xplane.OpMeta()).tf_op
        if tf_op and name not in parsed:
            found, op = path_scopes(tf_op)
            parsed[name] = (found, SVRG_UPDATE in found and op == KERNEL_OP)
        out.append((*parsed[name], True) if tf_op else None)
    shared: Dict[int, set] = {}
    for k, outer in enumerate(enclosing):
        if out[k] is None:
            continue
        for a in outer:
            if out[a] is None or not out[a][2]:
                shared[a] = shared.get(a, set(out[k][0])) & set(out[k][0])
                out[a] = ({x: None for x in shared[a]}, False, False)
    return out


def reduce_scopes(planes, metadata: Dict[str, Dict[str, xplane.OpMeta]],
                  lo_ns: int, hi_ns: int, chips: int) -> ScopeTrace:
    """Reduce profiler planes, and their ops' metadata from
    `xplane.op_metadata`, over the span [lo_ns, hi_ns) of the trace's
    clock."""
    scope_ns: Dict[str, int] = {}
    calls: Dict[str, int] = {}
    steps = named = 0
    busy, programs = [], []
    devices = sorted((p for p in planes if readings.DEVICE_PLANE.match(p.name)),
                     key=lambda p: int(readings.DEVICE_PLANE.match(p.name)
                                       .group(1)))
    parsed: Dict[str, Tuple[Dict[str, str], bool]] = {}
    for plane in devices[:chips]:
        ops, runs = [], []
        for line in plane.lines:
            if line.name not in (readings.OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                s = max(lo_ns, int(e.start_ns))
                t = min(hi_ns, int(e.start_ns + e.duration_ns))
                if t <= s:
                    continue
                if line.name == MODULES_LINE:
                    runs.append((s, t))
                else:
                    ops.append((s, t, e.name))
        ops.sort(key=lambda o: (o[0], -o[1]))
        runs.sort()
        busy.append(_union_s([(s, t) for s, t, _ in ops]))
        programs.append(_union_s(runs))
        starts = [s for s, _ in runs]
        own, enclosing = _nesting(ops)
        segment, last_run = 0, None
        seen: Dict[str, int] = {}
        for (s, _, _), own_ns, op in zip(
                ops, own, _op_scopes(ops, metadata.get(plane.name, {}),
                                     enclosing, parsed)):
            if op is None:
                continue
            found, is_kernel, has_tf_op = op
            named += has_tf_op
            run = bisect.bisect_right(starts, s) - 1
            if run != last_run:
                segment, last_run = segment + 1, run
            for scope, place in found.items():
                scope_ns[scope] = scope_ns.get(scope, 0) + own_ns
                if place is not None and seen.get(place) != segment:
                    seen[place] = segment
                    calls[scope] = calls.get(scope, 0) + 1
            if is_kernel:
                steps += 1
                segment += 1
    n = max(1, len(busy))
    return ScopeTrace(window_s=(hi_ns - lo_ns) / 1e9, busy_s=sum(busy) / n,
                      program_s=sum(programs) / n,
                      scope_s={k: v / 1e9 for k, v in scope_ns.items()},
                      scope_calls=calls, inner_steps=steps, named_ops=named,
                      chips_seen=len(busy))


def load(trace_dir: Path, window_s: float, chips: int) -> Optional[ScopeTrace]:
    """The reduction of the profile under ``trace_dir`` over the window
    `readings.load_device_trace` read: from the sync marker, ``window_s``
    long."""
    from jax.profiler import ProfileData
    found = glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    if not found:
        return None
    data = Path(found[0]).read_bytes()
    planes = list(ProfileData.from_serialized_xspace(data).planes)
    marker = next(e for p in planes if not readings.DEVICE_PLANE.match(p.name)
                  for line in p.lines for e in line.events
                  if e.name == readings.SYNC_MARKER)
    lo = int(marker.start_ns)
    return reduce_scopes(planes, xplane.op_metadata(data), lo,
                         lo + round(window_s * 1e9), chips)


def of(r) -> Optional[ScopeTrace]:
    """The run's scope reduction, read once per run and kept on ``r``."""
    if not hasattr(r, "scopes"):
        from chipbench import driver
        r.scopes = (None if r.trace is None else
                    load(driver.TRACE_DIR, r.trace.window_s, r.cell.chips))
    return r.scopes


def device_time(r, scope: str, per_step: bool) -> Optional[float]:
    """Seconds of device time in ``scope`` per inner step (``per_step``)
    or per call of the scope. None where the program names no scope (it
    predates them) or, off the chip, where there is nothing to read; on a
    TPU a trace whose ops carry no ``tf_op``, or that misses the scope
    while it holds others, is an error."""
    st = of(r)
    if st is None or st.named_ops == 0:
        if r.on_chip:
            raise RuntimeError("device trace: no device op carries a tf_op")
        return None
    if not st.scope_s:
        return None
    count = st.inner_steps if per_step else st.scope_calls.get(scope, 0)
    if scope not in st.scope_s or not count:
        if r.on_chip:
            raise RuntimeError(
                f"device trace: no op in scope {scope!r} or no "
                f"{'inner step' if per_step else 'call of it'} (scopes "
                f"seen: {sorted(st.scope_s)})")
        return None
    return st.scope_s[scope] / count
