"""Smoke test of the served sweep path on a TPU, at the paper's rcv1 size.

    python chip_smoke.py [--seed S]        # one chip: the served path
    python chip_smoke.py --chips 4         # four chips: row-sharded sweeps

One chip (the default). rcv1 is generated from ``--seed`` at its published
n = 20,242, hashed to the repo's width p = 2,048 (X is ~166 MB on the
device). A `SweepServer` (service + flush daemon + HTTP) serves three
tenants whose `SweepClient` requests together make the paper's §5 grid:
the three read schemes × two step sizes of AsySVRG at 10 threads and
τ = 9, one Hogwild! row and one serial SVRG row, three epochs each. The
grid is three compiled programs and five groups, one per algo and read
scheme; each AsySVRG group pools rows of two tenants. A second, warm wave
of the same shapes follows.

Four chips (``--chips 4``) runs only the row-sharded path: the same grid
on `make_sweep_mesh(4)` through `run_sweep(mesh=...)` and through
`SweepService(mesh=...)`, every group padded to a multiple of four rows.
Each row is compared bit for bit with an unsharded run on device 0 of the
rows its device holds, and its difference from unsharded runs of each row
alone and of the whole group is reported (on a TPU a row's bits depend on
the vmap width).

Every run refuses a non-TPU backend, a set ``REPRO_KERNEL_MODE``, and a
`svrg_update` kernel that does not resolve to the compiled Pallas body.
It checks that histories are finite and every row's loss fell, that each
row's last loss matches a NumPy float64 evaluation of f(final_w), and
that the served (or sharded) results equal a direct `run_sweep` at the
same width bit for bit. On one chip the
AsySVRG group is also rerun with ``REPRO_KERNEL_MODE=reference`` set inside
the process, which builds a separate runner around the jnp oracle of
`svrg_update`; its rows must agree with the compiled kernel's within
`REF_HIST_RTOL` / `REF_W_RTOL`. Progress lines
are JSON objects; the last line of stdout is
``{"ok": true, "device": {...}}`` only when every check passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import LogisticRegression, SweepSpec, run_sweep  # noqa: E402
from repro.core.sweep import (_row_args, group_label,  # noqa: E402
                              plan_sweep)
from repro.data.libsvm import make_synthetic_libsvm  # noqa: E402
from repro.kernels.dispatch import KERNEL_MODE_ENV, kernel_mode  # noqa: E402
from repro.launch.mesh import make_sweep_mesh  # noqa: E402
from repro.obs.trace import disable_tracing, enable_tracing, tracer  # noqa: E402
from repro.server import FlushPolicy, SweepClient, SweepServer  # noqa: E402
from repro.service import SweepService, cache_stats  # noqa: E402
from repro.service.cache import get_group_runner, uncounted_trace  # noqa: E402
from repro.utils.compile_cache import enable_compile_cache  # noqa: E402

EPOCHS = 3
DROP_PROB = 0.02                 # SweepService / run_sweep default
LOSS_RTOL = 1e-4                 # float32 engine vs float64 NumPy f(w)
# compiled svrg_update vs its jnp oracle over a whole 3-epoch group: two
# right lowerings differ by rounding only (~1e-7 on the CPU, interpret vs
# reference), while a learning rate skewed by 0.1% moves the histories by
# ~2.5e-4 and w by ~5e-4 of its largest entry
REF_HIST_RTOL = 1e-5
REF_W_RTOL = 1e-4
RESULT_TIMEOUT_S = 1200.0


class SmokeFailure(Exception):
    """A check of the smoke failed; the message says which."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ----------------------------------------------------------------- phases
def preflight(chips: int) -> dict:
    """Refuse anything but the compiled TPU path; returns the device as
    JAX reports it."""
    check(not os.environ.get(KERNEL_MODE_ENV),
          f"${KERNEL_MODE_ENV} is set; the smoke runs only the default path")
    backend = jax.default_backend()
    check(backend == "tpu", f"JAX backend is {backend!r}, not 'tpu'")
    devices = jax.devices()
    check(len(devices) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {len(devices)}")
    mode = kernel_mode()
    check(mode == "compiled",
          f"svrg_update resolves to {mode!r}, not 'compiled'")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def build_objective(seed: int, scale: float = 1.0) -> LogisticRegression:
    """rcv1 from ``seed``; ``scale`` < 1 only for rehearsals off the chip."""
    ds = make_synthetic_libsvm("rcv1", seed=seed, scale=scale)
    return LogisticRegression(ds.X, ds.y, l2_reg=ds.l2_reg)


def tenant_grids(wave: int) -> dict:
    """The paper's §5 grid split over three tenants: AsySVRG's three read
    schemes × two step sizes at 10 threads and τ = 9 (tenants a and b share
    each scheme's group), a Hogwild! row and a serial SVRG row. Waves
    differ only in their seeds, so every wave has the same compiled
    shapes."""
    def asy(scheme, step, seed):
        return SweepSpec(algo="asysvrg", scheme=scheme, step_size=step,
                         tau=9, num_threads=10, seed=seed)

    s = 100 * wave
    schemes = ("consistent", "inconsistent", "unlock")
    return {
        "tenant-a": [asy(sc, 1.0, s + i) for i, sc in enumerate(schemes)],
        "tenant-b": [asy(sc, 2.0, s + 10 + i)
                     for i, sc in enumerate(schemes)]
        + [SweepSpec(algo="hogwild", scheme="unlock", step_size=1.0, tau=9,
                     num_threads=10, seed=s + 13)],
        "tenant-c": [SweepSpec(algo="svrg", step_size=1.0, num_threads=1,
                               seed=s + 20)],
    }


def numpy_loss(obj: LogisticRegression, W: np.ndarray) -> np.ndarray:
    """f(w) for each row of W in float64 NumPy, independent of the engine."""
    X = np.asarray(obj.X, np.float64)
    y = np.asarray(obj.y, np.float64)
    W = np.asarray(W, np.float64)
    margins = y[:, None] * (X @ W.T)
    return (np.logaddexp(0.0, -margins).mean(axis=0)
            + 0.5 * obj.l2 * np.sum(W * W, axis=1))


def check_rows(obj: LogisticRegression, res, what: str) -> None:
    """Finite histories, every row's loss fell, and the last loss agrees
    with the float64 reference at the returned iterate."""
    check(np.isfinite(res.histories).all() and np.isfinite(res.final_w).all(),
          f"{what}: non-finite history or iterate")
    last = res.histories[:, -1]
    fell = last < res.histories[:, 0]
    check(fell.all(), f"{what}: rows {np.flatnonzero(~fell).tolist()} did "
          "not lower the loss")
    ref = numpy_loss(obj, res.final_w)
    rel = np.abs(last - ref) / np.abs(ref)
    check(bool((rel <= LOSS_RTOL).all()),
          f"{what}: last loss vs float64 f(final_w) rel err "
          f"{rel.max():.3g} > {LOSS_RTOL}")


def check_equal(res, histories, final_w, what: str) -> None:
    check(np.array_equal(res.histories, histories)
          and np.array_equal(res.final_w, final_w),
          f"{what}: results differ bit-wise")


def check_kernel_in_runner(obj, plan) -> None:
    """The asysvrg-engine runners that ran hold the compiled Pallas
    `svrg_update` kernel (a ``tpu_custom_call`` in their HLO)."""
    w_init = obj.init_flat()
    for key_, members in plan.groups.items():
        engine = key_.engine
        if engine != "asysvrg":
            continue
        runner = get_group_runner(engine, group_epochs=EPOCHS,
                                  total=key_.total, option=key_.option,
                                  buf_len=key_.buf_len, drop_prob=DROP_PROB,
                                  mesh=None, obj=obj)
        args = _row_args(engine, plan.specs, plan.resolved, members, w_init)
        with uncounted_trace():
            hlo = runner.lower(*obj.data_args(), *args).as_text()
        check("tpu_custom_call" in hlo,
              f"{group_label(key_)}: no tpu_custom_call in the runner's HLO")


def check_against_reference(obj, plan, specs, hist, final_w) -> None:
    """Rerun the AsySVRG rows (the asysvrg engine's groups of more than one
    row, one per scheme) with `svrg_update` on its jnp oracle and compare
    with the compiled kernel's rows of ``hist`` / ``final_w``."""
    keys = [k for k, m in plan.groups.items()
            if k.engine == "asysvrg" and len(m) > 1]
    members = [c for k in keys for c in plan.groups[k]]
    base = cache_stats()
    os.environ[KERNEL_MODE_ENV] = "reference"
    try:
        t0 = time.perf_counter()
        ref = run_sweep(obj, EPOCHS, [specs[c] for c in members],
                        drop_prob=DROP_PROB)
        wall = time.perf_counter() - t0
    finally:
        del os.environ[KERNEL_MODE_ENV]
    compiles = cache_stats().since(base).compiles
    check(compiles == 1, f"reference run compiled {compiles} program(s), "
          "not one runner of its own")
    hist_rel = float((np.abs(hist[members] - ref.histories)
                      / np.abs(ref.histories)).max())
    w_rel = float((np.abs(final_w[members] - ref.final_w).max(axis=1)
                   / np.abs(ref.final_w).max(axis=1)).max())
    emit("kernel_vs_reference", groups=[group_label(k) for k in keys],
         rows=len(members),
         wall_s=wall, max_rel_history_diff=hist_rel,
         max_rel_final_w_diff=w_rel)
    check(hist_rel <= REF_HIST_RTOL and w_rel <= REF_W_RTOL,
          f"compiled svrg_update vs reference: history rel {hist_rel:.3g} "
          f"(limit {REF_HIST_RTOL}), w rel {w_rel:.3g} (limit {REF_W_RTOL})")


def compile_seconds(tickets) -> dict:
    """Seconds of each runner call that traced and compiled, from the
    requests' `execute` spans (one per group; shared by its requests)."""
    out = {}
    for t in tickets:
        for span in (tracer().get(t.trace_id) or {}).get("spans", ()):
            tags = span["tags"]
            if span["name"] == "execute" and tags.get("compiled"):
                out[f"{tags['engine']}-M{tags['total']}"] = \
                    span["duration_ms"] / 1000.0
    return out


def http_wave(server: SweepServer, grids: dict):
    """The tenants submit their grids concurrently over HTTP; returns
    ({tenant: (ticket, result)}, wall seconds from first submit to last
    result)."""
    out, errors = {}, []

    def tenant(name):
        try:
            client = SweepClient(server.url, timeout=60.0)
            ticket = client.submit(grids[name], tenant=name)
            out[name] = (ticket, client.result(ticket,
                                               timeout=RESULT_TIMEOUT_S))
        except Exception as e:           # reported below, on the main thread
            errors.append(f"{name}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=tenant, args=(name,))
               for name in grids]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join(RESULT_TIMEOUT_S + 60.0)
    wall = time.perf_counter() - t0
    check(not errors and not any(t.is_alive() for t in threads),
          f"tenant request failed: {errors}")
    return out, wall


def serve_waves(obj: LogisticRegression) -> None:
    """The one-chip path: a cold HTTP wave, a direct `run_sweep` of each
    compiled group for bit-equality and warm timing, then a warm HTTP wave
    that must compile nothing."""
    grids = tenant_grids(0)
    rows = sum(len(g) for g in grids.values())
    svc = SweepService(obj, epochs=EPOCHS, drop_prob=DROP_PROB)
    # the size trigger fires once every tenant's rows are queued, so each
    # wave is one coalesced flush; the deadline is only a backstop
    policy = FlushPolicy(max_rows=rows, max_delay_ms=RESULT_TIMEOUT_S * 1e3,
                         heartbeat_stall_s=RESULT_TIMEOUT_S)
    server = SweepServer(svc, policy=policy).start()
    try:
        enable_tracing()
        base = cache_stats()
        cold, cold_wall = http_wave(server, grids)
        disable_tracing()
        compiles = cache_stats().since(base).compiles
        emit("cold_wave", wall_s=cold_wall, compiles=compiles,
             compile_s=compile_seconds(t for t, _ in cold.values()))

        # the flush pooled rows in request-id order; a direct run_sweep of
        # each group's rows in that order has the group's width and row
        # order, so it runs the executable the server ran
        order = sorted(cold, key=lambda name: int(cold[name][0]))
        specs = [s for name in order for s in grids[name]]
        offsets = np.cumsum([0] + [len(grids[name]) for name in order])
        plan = plan_sweep(obj, EPOCHS, specs)
        check_kernel_in_runner(obj, plan)
        base = cache_stats()
        hist = np.zeros((len(specs), EPOCHS + 1), np.float32)
        final_w = np.zeros((len(specs), obj.flat_dim), np.float32)
        for key_, members in plan.groups.items():
            t0 = time.perf_counter()
            res = run_sweep(obj, EPOCHS, [specs[c] for c in members],
                            drop_prob=DROP_PROB)
            wall = time.perf_counter() - t0
            hist[members], final_w[members] = res.histories, res.final_w
            emit("direct_group", group=group_label(key_), rows=len(members),
                 warm_wall_s=wall,
                 row_epochs_per_s=len(members) * EPOCHS / wall)
        check(cache_stats().since(base).compiles == 0,
              "direct run_sweep compiled instead of reusing the server's "
              "runners")
        for i, name in enumerate(order):
            res = cold[name][1]
            lo, hi = offsets[i], offsets[i + 1]
            check_equal(res, hist[lo:hi], final_w[lo:hi],
                        f"{name}: HTTP vs direct run_sweep")
            check_rows(obj, res, f"cold wave {name}")

        base = cache_stats()
        warm, warm_wall = http_wave(server, tenant_grids(1))
        warm_compiles = cache_stats().since(base).compiles
        for name, (_, res) in warm.items():
            check_rows(obj, res, f"warm wave {name}")
        emit("warm_wave", wall_s=warm_wall, compiles=warm_compiles,
             rows=rows, row_epochs_per_s=rows * EPOCHS / warm_wall)
        check(warm_compiles == 0,
              f"warm wave compiled {warm_compiles} program(s)")
        stats = svc.stats()
        emit("service", flushes=stats.flushes,
             rows_coalesced=stats.rows_coalesced,
             groups_dispatched=stats.groups_dispatched)
        check_against_reference(obj, plan, specs, hist, final_w)
    finally:
        disable_tracing()
        server.stop()


def unsharded_by_width(obj: LogisticRegression, specs, plan, width_of):
    """(histories, final_w) of unsharded runs on device 0 in which each
    group's rows run in chunks of ``width_of(members)`` rows, the last chunk
    padded with copies of the group's first row as
    `repro.core.sweep._pad_rows` pads a sharded group. With
    ``ceil(rows / ndev)`` the chunks are exactly the rows each device of an
    ``ndev``-wide `data` axis holds, at the sharded program's width."""
    hist = np.zeros((len(specs), EPOCHS + 1), np.float32)
    final_w = np.zeros((len(specs), obj.flat_dim), np.float32)
    for members in plan.groups.values():
        width = width_of(members)
        padded = members + [members[0]] * (-len(members) % width)
        for lo in range(0, len(members), width):
            shard = padded[lo:lo + width]
            res = run_sweep(obj, EPOCHS, [specs[c] for c in shard],
                            drop_prob=DROP_PROB)
            for i, c in enumerate(members[lo:lo + width]):
                hist[c], final_w[c] = res.histories[i], res.final_w[i]
    return hist, final_w


def sharded_waves(obj: LogisticRegression, mesh) -> None:
    """The four-chip path: the same grid row-sharded over ``mesh`` through
    `run_sweep` and `SweepService`. Every row must be bit-equal to the
    unsharded run of the rows its device holds (no collective crosses
    rows). Against unsharded runs of each row alone and of each whole
    group the difference is reported: on TPU a row's bits depend on the
    vmap width (ROADMAP D1)."""
    grids = tenant_grids(0)
    specs = [s for g in grids.values() for s in g]
    ndev = int(mesh.shape["data"])
    t0 = time.perf_counter()
    ref = run_sweep(obj, EPOCHS, specs, drop_prob=DROP_PROB)
    emit("unsharded", wall_s=time.perf_counter() - t0, rows=len(specs))
    check_rows(obj, ref, "unsharded")
    plan = plan_sweep(obj, EPOCHS, specs)
    check_kernel_in_runner(obj, plan)
    local_hist, local_w = unsharded_by_width(
        obj, specs, plan, lambda members: -(-len(members) // ndev))
    alone_hist, alone_w = unsharded_by_width(obj, specs, plan,
                                             lambda members: 1)

    t0 = time.perf_counter()
    sharded = run_sweep(obj, EPOCHS, specs, drop_prob=DROP_PROB, mesh=mesh)
    emit("sharded_run_sweep", wall_s=time.perf_counter() - t0,
         devices=ndev, group_rows=[len(m) for m in plan.groups.values()])
    check_rows(obj, sharded, "sharded run_sweep")
    check_equal(sharded, local_hist, local_w,
                "sharded run_sweep vs unsharded per-device rows")
    for width, hist, w in (("1", alone_hist, alone_w),
                           ("group", ref.histories, ref.final_w)):
        emit("sharded_vs_unsharded_at_width", width=width,
             rows_bit_equal=[bool(np.array_equal(sharded.histories[c],
                                                 hist[c])
                                  and np.array_equal(sharded.final_w[c],
                                                     w[c]))
                             for c in range(len(specs))],
             max_abs_history_diff=float(np.abs(
                 sharded.histories - hist).max()),
             max_abs_final_w_diff=float(np.abs(sharded.final_w - w).max()))

    svc = SweepService(obj, epochs=EPOCHS, drop_prob=DROP_PROB, mesh=mesh)
    t0 = time.perf_counter()
    ids = {name: svc.submit(g, tenant=name) for name, g in grids.items()}
    svc.flush()
    lo = 0
    for name, g in grids.items():
        hi = lo + len(g)
        check_equal(svc.result(ids[name]), local_hist[lo:hi],
                    local_w[lo:hi],
                    f"sharded SweepService {name} vs unsharded per-device "
                    "rows")
        lo = hi
    emit("sharded_service", wall_s=time.perf_counter() - t0,
         rows_coalesced=svc.stats().rows_coalesced)


def peak_bytes() -> dict:
    out = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            out[str(d.id)] = int(stats["peak_bytes_in_use"])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated rcv1 data")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the row-sharded four-chip path")
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()       # before anything compiles
    try:
        device = preflight(args.chips)
        emit("device", **device, jax=jax.__version__,
             compile_cache=cache_dir)
        t0 = time.perf_counter()
        obj = build_objective(args.seed)
        emit("data", n=obj.n, p=obj.p, x_bytes=int(obj.X.nbytes),
             setup_s=time.perf_counter() - t0)
        if args.chips == 4:
            sharded_waves(obj, make_sweep_mesh(4))
        else:
            serve_waves(obj)
        emit("memory", peak_bytes_in_use=peak_bytes())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
