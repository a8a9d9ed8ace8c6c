"""Compile a cell's programs for a described TPU v5e, with no chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python benchmarks/chip/rehearse_aot.py \
        --workload rcv1.sequential_tuner [--devices 4]

For the cell's configuration at its published n and p, this compiles with
the TPU compiler installed here: every group runner that one round of the
cell's traffic dispatches, at the width the flush gives it (with
``--devices 4``, row-sharded over a 4-device `data` axis, each device at
its share of the width), the reference's epoch in float32 and bfloat16,
and the data generator. It prints one JSON line per program with the
bytes `memory_analysis()` gives, whether a Pallas kernel is in it, and
whether any collective is. Nothing runs, so it says nothing of time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import types
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import (AxisType, Mesh, NamedSharding,  # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

from chipbench import cells, traffic  # noqa: E402
from objectives import logreg_reference  # noqa: E402


def _bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes + m.generated_code_size_in_bytes
               - m.alias_size_in_bytes)


def _report(what: str, compiled) -> None:
    hlo = compiled.as_text()
    print(json.dumps({"program": what, "device_bytes": _bytes(compiled),
                      "kernel": "tpu_custom_call" in hlo,
                      "collective": any(c in hlo for c in (
                          "all-gather", "all-reduce", "all-to-all",
                          "collective-permute"))}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--devices", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)

    from jax.experimental import topologies
    from repro.core import LogisticRegression, SweepSpec
    from repro.core.sweep import (_group_fn, _pad_rows, _resolve, _row_args,
                                  _shard_group_fn)
    from repro.kernels import dispatch

    jax.config.update("jax_enable_compilation_cache", False)
    dispatch.kernel_backend = lambda: "tpu"     # trace the compiled kernel
    cell = cells.load_cell(args.workload)
    n, p = int(cell.config["n"]), int(cell.config["p"])
    epochs = int(cell.mix["epochs"])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if args.devices == 1:
        mesh = None
        data_sh = row_sh = SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",),
                    axis_types=(AxisType.Auto,))
        data_sh, row_sh = NamedSharding(mesh, P()), NamedSharding(mesh,
                                                                  P("data"))
    shape_only = types.SimpleNamespace(n=n)
    obj = LogisticRegression(np.zeros((1, p), np.float32),
                             np.ones(1, np.float32), cell.config["l2"])

    rows = [r for i, t in enumerate(traffic.tenants(cell.mix))
            for r in traffic.request_rows(t, i, -1, 0)]
    specs = [SweepSpec(**r) for r in rows]
    resolved = [_resolve(shape_only, s, epochs) for s in specs]
    groups = {}
    for c, r in enumerate(resolved):
        groups.setdefault((r.engine, r.total, r.option, r.buf_len),
                          []).append(c)
    data = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=data_sh)
            for s in ((n, p), (n,), ())]
    for (engine, total, option, buf_len), members in groups.items():
        fn, num_row = _group_fn(engine, obj=obj, num_data=3, epochs=epochs,
                                total=total, buf_len=buf_len, option=option,
                                drop_prob=float(cell.mix["drop_prob"]))
        row_args = _row_args(engine, specs, resolved, members,
                             obj.init_flat())
        if mesh is not None:
            fn = _shard_group_fn(fn, mesh, 3, num_row)
            row_args = _pad_rows(row_args, -len(members) % 4)
        row_args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=row_sh)
                    for a in row_args]
        compiled = jax.jit(fn).lower(*data, *row_args).compile()
        _report(f"{engine}-M{total}-buf{buf_len}-rows{len(members)}",
                compiled)

    one = SingleDeviceSharding(topo.devices[0])
    for row in {(r["algo"], r.get("scheme")): r for r in rows}.values():
        total, tau, scheme = logreg_reference.resolve(row, n)
        for dtype in (jnp.float32, jnp.bfloat16):
            shapes = [jax.ShapeDtypeStruct(s, dtype, sharding=one)
                      for s in ((n, p), (n,), (), (p,))]
            key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
            step = jax.ShapeDtypeStruct((), dtype, sharding=one)
            compiled = logreg_reference._epoch.lower(
                *shapes, key, step, algo=row["algo"], scheme=scheme,
                total=total, tau=tau,
                drop_prob=float(cell.mix["drop_prob"]),
                option=int(row.get("option", 2))).compile()
            _report(f"reference-{row['algo']}-{scheme}-"
                    f"{jnp.dtype(dtype).name}", compiled)
    gen = cell.objective._generate.lower(
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one), n=n, p=p,
        nnz=int(cell.config["nnz_per_row"])).compile()
    _report("generate", gen)
    return 0


if __name__ == "__main__":
    sys.exit(main())
