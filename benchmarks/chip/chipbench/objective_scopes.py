"""The device trace by the scope names an objective kind declares.

`chipbench.scopes` reduces the epoch cores' names (its own ``SCOPES``).
An objective kind's module may name its own device work as well
(``SCOPES`` in ``objectives/<kind>.py``); this reduces the same profile
for those names, by the rules of `chipbench.scopes.reduce_scopes`: an op
counts for each name on its ``tf_op`` path by its own time; an op without
a ``tf_op`` (a loop's ``while``) counts for the names every op nested in
it shares; a name has one call for each place it comes from in each
segment of ops between `svrg_update` calls and program-run starts. The
inner steps are `chipbench.scopes`' count of kernel calls.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple

from chipbench import readings, scopes, xplane


@dataclasses.dataclass
class NamedTime:
    scope_s: Dict[str, float]        # name -> device seconds, all chips
    scope_calls: Dict[str, int]      # name -> calls, all chips


def path_names(tf_op: str, names: Sequence[str]) -> Dict[str, str]:
    """{name of ``names`` on an op's ``tf_op`` path: the path up to it},
    read as `chipbench.scopes.path_scopes` reads its own names."""
    parts = tf_op.split("/")[:-1]
    found: Dict[str, str] = {}
    for i, part in enumerate(parts):
        while (m := scopes._TRANSFORM.match(part)):
            part = m.group(1)
        if part in names and part not in found:
            found[part] = "/".join(parts[:i + 1])
    return found


def _tag(tf_op: str, names) -> Tuple[Dict[str, str], bool]:
    """(the op's names and places, whether it is an `svrg_update` call)."""
    epoch, op = scopes.path_scopes(tf_op)
    return (path_names(tf_op, names),
            scopes.SVRG_UPDATE in epoch and op == scopes.KERNEL_OP)


def reduce_named(planes, metadata: Dict[str, Dict[str, xplane.OpMeta]],
                 lo_ns: int, hi_ns: int, chips: int,
                 names: Sequence[str]) -> NamedTime:
    """Reduce profiler planes over [lo_ns, hi_ns) for ``names``, as
    `chipbench.scopes.reduce_scopes` reduces its own."""
    scope_ns: Dict[str, int] = {}
    calls: Dict[str, int] = {}
    parsed: Dict[str, Tuple[Dict[str, str], bool]] = {}
    devices = sorted((p for p in planes if readings.DEVICE_PLANE.match(p.name)),
                     key=lambda p: int(readings.DEVICE_PLANE.match(p.name)
                                       .group(1)))
    for plane in devices[:chips]:
        ops, starts = [], []
        for line in plane.lines:
            if line.name not in (readings.OPS_LINE, scopes.MODULES_LINE):
                continue
            for e in line.events:
                s = max(lo_ns, int(e.start_ns))
                t = min(hi_ns, int(e.start_ns + e.duration_ns))
                if t <= s:
                    continue
                if line.name == scopes.MODULES_LINE:
                    starts.append(s)
                else:
                    ops.append((s, t, e.name))
        ops.sort(key=lambda o: (o[0], -o[1]))
        starts.sort()
        own, enclosing = scopes._nesting(ops)
        meta = metadata.get(plane.name, {})
        tags = []
        for _, _, name in ops:
            tf_op = meta.get(name, xplane.OpMeta()).tf_op
            if tf_op and name not in parsed:
                parsed[name] = _tag(tf_op, names)
            tags.append(parsed[name] if tf_op else None)
        shared: Dict[int, set] = {}
        for k, outer in enumerate(enclosing):
            if tags[k] is None:
                continue
            for a in outer:
                if tags[a] is None:
                    shared[a] = shared.get(a, set(tags[k][0])) & set(
                        tags[k][0])
        segment, last_run = 0, None
        seen: Dict[str, int] = {}
        for k, (s, _, _) in enumerate(ops):
            if k in shared:
                found, is_kernel = dict.fromkeys(shared[k]), False
            elif tags[k] is None:
                continue
            else:
                found, is_kernel = tags[k]
            run = bisect.bisect_right(starts, s) - 1
            if run != last_run:
                segment, last_run = segment + 1, run
            for name, place in found.items():
                scope_ns[name] = scope_ns.get(name, 0) + own[k]
                if place is not None and seen.get(place) != segment:
                    seen[place] = segment
                    calls[name] = calls.get(name, 0) + 1
            if is_kernel:
                segment += 1
    return NamedTime(scope_s={k: v / 1e9 for k, v in scope_ns.items()},
                     scope_calls=calls)


def load(trace_dir: Path, window_s: float, chips: int,
         names: Sequence[str]) -> Optional[NamedTime]:
    """The reduction of the profile under ``trace_dir`` over the window
    `chipbench.scopes.load` reads."""
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
    return reduce_named(planes, xplane.op_metadata(data), lo,
                        lo + round(window_s * 1e9), chips, names)


def of(r) -> Optional[NamedTime]:
    """The run's reduction for its objective kind's names, read once per
    run and kept on ``r``."""
    if not hasattr(r, "objective_scopes"):
        from chipbench import driver
        names = getattr(r.cell.objective, "SCOPES", ())
        r.objective_scopes = (
            None if r.trace is None or not names else
            load(driver.TRACE_DIR, r.trace.window_s, r.cell.chips, names))
    return r.objective_scopes


def device_time(r, name: str, per_step: bool) -> Optional[float]:
    """Seconds of device time in the objective's scope ``name`` per inner
    step (``per_step``) or per call of it. None off the chip where there
    is nothing to read; on a TPU a trace whose ops carry no ``tf_op``, or
    that misses the scope or its count, is an error, as in
    `chipbench.scopes.device_time`."""
    epoch = scopes.of(r)
    if epoch is None or epoch.named_ops == 0:
        if r.on_chip:
            raise RuntimeError("device trace: no device op carries a tf_op")
        return None
    st = of(r)
    seconds = st.scope_s.get(name) if st else None
    count = (epoch.inner_steps if per_step else
             st.scope_calls.get(name, 0) if st else 0)
    if not seconds or not count:
        if r.on_chip:
            raise RuntimeError(
                f"device trace: no op in scope {name!r} or no "
                f"{'inner step' if per_step else 'call of it'} (scopes "
                f"seen: {sorted(st.scope_s) if st else []})")
        return None
    return seconds / count
