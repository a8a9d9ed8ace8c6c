"""One run of one cell: set-up, a closed loop over HTTP for ``--seconds``,
then the check against the plain reference.

Set-up (``setup_s``, from process start to the window's start): the data is
generated on the device from ``--seed``; a `SweepServer` (service, flush
daemon, HTTP) starts over it, row-sharded over a `data` mesh of the cell's
chips where it has more than one; every tenant sends one warm-up request,
so exactly the cell's group shapes compile, or load from the persistent
cache. Then each tenant thread sends its requests with a `SweepClient` and
waits for each answer, until the window closes; requests in flight then
are waited for and not counted. With ``--trace 1`` the service's span
recorder is on for the window and the JAX profiler records its first
``trace_seconds``; the run reports the per-layer metrics instead of the
end-to-end ones, and leaves the trace under ``.cache/trace`` for a look by
hand until the next traced run replaces it.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time
from typing import Dict, List, Optional

from chipbench import cells, check, readings, traffic

CACHE_DIR = cells.BENCH_DIR / ".cache" / "jax"
TRACE_DIR = cells.BENCH_DIR / ".cache" / "trace"
GUARDED_ENV = ("REPRO_KERNEL_MODE", "REPRO_SWEEP_ENGINE")
REQUEST_TIMEOUT_S = 300.0
CLOSE_GRACE_S = 60.0


class Refused(Exception):
    """The run cannot measure here; it prints no result."""


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()[:chips]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def preflight(cell: cells.Cell) -> None:
    import jax
    from repro.kernels.dispatch import kernel_mode
    for var in GUARDED_ENV:
        if os.environ.get(var):
            raise Refused(f"${var} is set; the benchmark runs the default "
                          "path only")
    if jax.default_backend() != "tpu":
        raise Refused(f"JAX backend is {jax.default_backend()!r}, not tpu")
    if len(jax.devices()) != cell.chips:
        raise Refused(f"{cell.name} needs {cell.chips} chip(s), JAX sees "
                      f"{len(jax.devices())}")
    if kernel_mode() != "compiled":
        raise Refused("svrg_update does not resolve to the compiled kernel")


class Tenant(threading.Thread):
    """A closed-loop client: submit, wait for the answer, submit again."""

    def __init__(self, url: str, index: int, tenant: dict, seed: int,
                 epochs: int, close_at: Optional[float]):
        super().__init__(name=f"tenant-{tenant['name']}", daemon=True)
        self.url, self.index, self.tenant = url, index, tenant
        self.seed, self.epochs = seed, epochs
        self.close_at = close_at        # None: one warm-up request
        self.done: List[dict] = []
        self.failed = 0

    def request(self, client, k: int) -> Optional[dict]:
        from repro.core import SweepSpec
        rows = traffic.request_rows(self.tenant, self.index, k, self.seed)
        t0 = time.monotonic()
        try:
            ticket = client.submit([SweepSpec(**r) for r in rows],
                                   self.epochs, tenant=self.tenant["name"])
            res = client.result(ticket, timeout=REQUEST_TIMEOUT_S)
        except Exception as e:          # counted as failed, reported below
            print(f"request {self.tenant['name']}#{k} failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            self.failed += 1
            return None
        t1 = time.monotonic()
        return {"rows": rows, "t_submit": t0, "t_done": t1,
                "trace_id": ticket.trace_id,
                "row_epochs": int(res.epochs_per_row.sum()),
                "w": res.final_w, "losses": res.histories}

    def run(self) -> None:
        from repro.server import SweepClient
        client = SweepClient(self.url, timeout=REQUEST_TIMEOUT_S)
        if self.close_at is None:
            self.request(client, -1)
            return
        k = 0
        while time.monotonic() < self.close_at:
            req = self.request(client, k)
            if req is not None:
                self.done.append(req)
            k += 1


def drive(url: str, mix: dict, seed: int, close_at: Optional[float]):
    """One warm-up round (``close_at`` None) or the window's closed loop;
    returns the tenant threads once every one has ended."""
    threads = [Tenant(url, i, t, seed, int(mix["epochs"]), close_at)
               for i, t in enumerate(traffic.tenants(mix))]
    for th in threads:
        th.start()
    limit = time.monotonic() + REQUEST_TIMEOUT_S + CLOSE_GRACE_S
    if close_at is not None:
        limit += close_at - time.monotonic()
    for th in threads:
        th.join(max(0.0, limit - time.monotonic()))
    alive = [th.name for th in threads if th.is_alive()]
    if alive:
        raise RuntimeError(f"tenants still waiting after the grace time: "
                           f"{alive}")
    return threads


def end_to_end(done: List[dict], window_start: float,
               setup_s: float) -> Dict[str, float]:
    """The window's end-to-end numbers: all the row-epochs completed in it
    over the time to the last completion in it."""
    last = max(r["t_done"] for r in done)
    return {"row_epochs_per_s": sum(r["row_epochs"] for r in done)
            / (last - window_start),
            "setup_s": setup_s}


def run(args, t_start: float, *, require_chip: bool = True,
        config_override: Optional[dict] = None,
        mix_override: Optional[dict] = None,
        keep: Optional[dict] = None) -> dict:
    """Everything after argument parsing; returns the result line's dict.
    Tests pass ``require_chip=False`` and a small ``config_override``;
    `limits.py` widens the check with ``mix_override`` and takes the data
    and each checked row's gaps back in ``keep``."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from repro.launch.mesh import make_sweep_mesh
    from repro.obs import trace as obs_trace
    from repro.server import FlushPolicy, SweepServer
    from repro.service import SweepService, cache_stats

    cell = cells.load_cell(args.workload)
    cell.config.update(config_override or {})
    cell.mix.update(mix_override or {})
    if require_chip:
        preflight(cell)
    mix = cell.mix
    data = cell.objective.generate(cell.config, args.seed)
    jax.block_until_ready(data)
    mesh = make_sweep_mesh(cell.chips) if cell.chips > 1 else None
    svc = SweepService(cell.objective.program(cell.config, data),
                       epochs=int(mix["epochs"]),
                       drop_prob=float(mix["drop_prob"]), mesh=mesh)
    policy = FlushPolicy(max_rows=int(mix["flush"]["max_rows"]),
                         max_delay_ms=float(mix["flush"]["max_delay_ms"]),
                         heartbeat_stall_s=REQUEST_TIMEOUT_S)
    server = SweepServer(svc, policy=policy).start()
    profiling = False
    try:
        warm = drive(server.url, mix, args.seed, None)
        if any(th.failed for th in warm):
            raise RuntimeError("a warm-up request failed")
        compiled_before = cache_stats().compiles
        stats_before = svc.stats()
        if args.trace:
            obs_trace.enable_tracing(max_traces=1 << 16)
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            # device operations and the benchmark's own annotations only:
            # tracing every Python call would slow the host it measures
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level, options.host_tracer_level = 0, 1
            jax.profiler.start_trace(str(TRACE_DIR),
                                     profiler_options=options)
            profiling = True
            with jax.profiler.TraceAnnotation(readings.SYNC_MARKER):
                sync_ns = time.monotonic_ns()
        window_start = time.monotonic()
        setup_s = window_start - t_start
        close_at = window_start + args.seconds
        if args.trace:
            stopped: Dict[str, int] = {}
            stopper = threading.Thread(
                target=_stop_profiler, daemon=True,
                args=(window_start + float(mix["trace_seconds"]), stopped))
            stopper.start()
        tenants = drive(server.url, mix, args.seed, close_at)
        if args.trace:
            stopper.join()
            profiling = False
        compiles = cache_stats().compiles - compiled_before
        stats_after = svc.stats()
        device = device_info(cell.chips)
    finally:
        if profiling:
            jax.profiler.stop_trace()
        server.stop()
    if compiles:
        print(f"warning: {compiles} program(s) compiled inside the window",
              file=sys.stderr)

    attempted = sum(len(th.done) + th.failed for th in tenants)
    failed = sum(th.failed for th in tenants)
    done = [r for th in tenants for r in th.done if r["t_done"] <= close_at]
    if not done:
        raise RuntimeError("no request completed inside the window")
    e2e = end_to_end(done, window_start, setup_s)
    if args.trace:
        t_read = time.monotonic()
        rd = readings.Readings(
            cell=cell, device=device, done=done,
            spans=[obs_trace.tracer().get(r["trace_id"]) or {"spans": []}
                   for r in done],
            trace=readings.load_device_trace(TRACE_DIR, sync_ns,
                                             stopped["ns"], cell.chips))
        if rd.trace is None:
            raise RuntimeError("the profiler wrote no trace")
        metrics = rd.per_layer_metrics()
        print(f"trace read in {time.monotonic() - t_read:.1f} s",
              file=sys.stderr)
        device.update(busy_s=rd.trace.busy_s, window_s=rd.trace.window_s)
        extra = {"breakdown": rd.breakdown()}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        extra = {}
    print(json.dumps({"window": {
        "requests": len(done),
        "compiles_in_window": compiles,
        "flushes": stats_after.flushes - stats_before.flushes}}),
        file=sys.stderr)

    picked = check.sample(done, int(mix["check_per_kind"]), args.seed)
    print(f"checking {len(picked)} of {len(done)} requests against the "
          "reference", file=sys.stderr)
    limits = cell.config["limits"]
    try:
        rows = check.compare(picked, cell.objective.reference, data,
                             cell.config, mix)
        readings_ = check.worst(rows)
    except Exception as e:             # a malformed answer is a wrong one
        print(f"check failed: {type(e).__name__}: {e}", file=sys.stderr)
        rows, readings_ = [], {name: float("inf") for name in limits}
    if keep is not None:
        keep.update(data=data, cell=cell, checked=rows, picked=picked)
    checks = {name: {"value": readings_[name], "limit": limits[name]}
              for name in limits}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, **extra,
            "checks": checks}


def _stop_profiler(stop_at: float, stopped: Dict[str, int]) -> None:
    import jax
    time.sleep(max(0.0, stop_at - time.monotonic()))
    stopped["ns"] = time.monotonic_ns()
    jax.profiler.stop_trace()


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    args = parse_args(argv)
    try:
        result = run(args, t_start)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
