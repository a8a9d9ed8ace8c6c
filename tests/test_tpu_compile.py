"""Compiles of the main path's group runners for a described TPU v5e.

Nothing here runs on a chip. The TPU compiler installed with JAX compiles
for a `v5e:2x2` topology that is described, not attached, at rcv1's
published n = 20,242 and the repo's hashed width p = 2,048 — the size
`chip_smoke.py` runs — so a kernel Mosaic refuses, a program that does
not fit the chip's memory, or a collective in the row-sharded runner fails
here at no chip time. The topology is described inside a module fixture,
never at import, and the compile tests skip where it cannot be described.
Runners are built with `_group_fn` directly, not through the shared
runner cache, and the backend the kernel dispatch sees is steered with
``monkeypatch``: JAX itself still runs on the CPU.
"""
import os
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import LogisticRegression, SweepSpec
from repro.core.asysvrg import DROP_MASK_SCOPE, SCHEME_IDS
from repro.core.sweep import (_group_fn, _pad_rows, _resolve, _row_args,
                              _shard_group_fn, plan_sweep)
from repro.data.libsvm import PAPER_DATASETS
from repro.kernels import dispatch

N = PAPER_DATASETS["rcv1"]["n"]
DIM = PAPER_DATASETS["rcv1"]["p_reduced"]
EPOCHS = 3
HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # libtpu reads this when it loads; unset, the TPU compiler writes
        # its logs under /tmp
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a program compiled for a described chip cannot be read back from
        # the persistent cache, so none of these compiles may enter it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield desc
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


@pytest.fixture
def tpu_backend(monkeypatch):
    """The kernel dispatch sees a TPU, so the Pallas body traces compiled."""
    monkeypatch.delenv(dispatch.KERNEL_MODE_ENV, raising=False)
    monkeypatch.setattr(dispatch, "kernel_backend", lambda: "tpu")


def _grid(algo):
    """chip_smoke's grid, one compiled group per algo."""
    if algo == "hogwild":
        return [SweepSpec(algo="hogwild", scheme="unlock", step_size=1.0,
                          tau=9, num_threads=10)]
    return [SweepSpec(scheme=s, step_size=st, tau=9, num_threads=10)
            for s in ("consistent", "inconsistent", "unlock")
            for st in (1.0, 2.0)]


def _compile(algo, data_sharding, row_sharding, mesh=None, specs=None):
    """AOT-compile one group runner at rcv1 full size, for ``specs`` (by
    default `_grid`'s). Only shapes enter: the objective's pure methods
    take the data at call time, and `_resolve` reads nothing of the
    objective but n."""
    obj = LogisticRegression(np.zeros((1, DIM), np.float32),
                             np.ones(1, np.float32))
    specs = specs or _grid(algo)
    resolved = [_resolve(types.SimpleNamespace(n=N), s, EPOCHS)
                for s in specs]
    r = resolved[0]
    fn, num_row = _group_fn(r.engine, obj=obj, num_data=3, epochs=EPOCHS,
                            total=r.total, buf_len=r.buf_len,
                            option=r.option, drop_prob=0.02)
    rows = _row_args(r.engine, specs, resolved, range(len(specs)),
                     obj.init_flat())
    if mesh is not None:
        fn = _shard_group_fn(fn, mesh, 3, num_row)
        rows = _pad_rows(rows, -len(specs) % mesh.devices.size)
    data = [jax.ShapeDtypeStruct(shape, jnp.float32, sharding=data_sharding)
            for shape in ((N, DIM), (N,), ())]
    rows = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=row_sharding)
            for a in rows]
    return jax.jit(fn).lower(*data, *rows).compile()


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes)


def test_vmap_asysvrg_runner_compiles_with_kernel(topo, tpu_backend):
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = _compile("asysvrg", one_chip, one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


def test_compiled_kernel_call_carries_its_scopes(topo, tpu_backend):
    """A device trace names each op by its compiled instruction and keeps
    its name stack (``tf_op``): the kernel's call is the `svrg_update`
    Pallas call inside the inner step, one in each scheme's inner loop,
    so a trace reader can count steps by it."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    hlo = _compile("asysvrg", one_chip, one_chip).as_text()
    calls = [line for line in hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == len(SCHEME_IDS)
    for call in calls:
        op_name = re.search(r'op_name="([^"]*)"', call).group(1)
        parts = op_name.split("/")
        assert parts[-1] == "pallas_call"
        assert parts.index("inner_step") < parts.index("svrg_update")
        assert call.lstrip().startswith("%svrg_update")


def test_hogwild_runner_compiles(topo, tpu_backend):
    one_chip = SingleDeviceSharding(topo.devices[0])
    compiled = _compile("hogwild", one_chip, one_chip)
    assert _device_bytes(compiled) < HBM_BYTES


def test_sharded_runner_compiles_without_collectives(topo, tpu_backend):
    """Six rows padded to eight over a 4-device `data` axis: each device
    runs its rows alone, so the program holds no collective."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",),
                axis_types=(AxisType.Auto,))
    compiled = _compile("asysvrg", NamedSharding(mesh, P()),
                        NamedSharding(mesh, P("data")), mesh=mesh)
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-gather" not in hlo and "all-reduce" not in hlo


def _coordinate_gathers(hlo, operand_shape):
    """The compiled module's gathers that read single coordinates out of an
    operand of ``operand_shape``: a gather whose slice is narrower than the
    operand's last axis. HLO instruction names are unique in a module, so
    an operand's shape is found at its definition."""
    shapes = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = \w+\[([\d,]*)\]", hlo,
                             re.MULTILINE))
    found = []
    for line in hlo.splitlines():
        call = re.search(r"\bgather\(%([^,\s)]+)", line)
        if call is None:
            continue
        shape = shapes.get(call.group(1), "")
        sizes = re.search(r"slice_sizes=\{([\d,]*)\}", line).group(1)
        if (shape == ",".join(map(str, operand_shape))
                and int(sizes.split(",")[-1]) < operand_shape[-1]):
            found.append(line.strip())
    return found


@pytest.mark.parametrize("algo", ["asysvrg", "hogwild"])
def test_unlock_read_compiles_without_a_coordinate_gather(topo, tpu_backend,
                                                          algo):
    """The unlock reader selects over the ring buffer's slots: no gather
    reads single coordinates out of the (rows, buf_len, p) buffer. The
    locked readers' whole-row reads may stay gathers."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    hlo = _compile(algo, one_chip, one_chip).as_text()
    resolved = _resolve(types.SimpleNamespace(n=N), _grid(algo)[0], EPOCHS)
    buffer_shape = (len(_grid(algo)), resolved.buf_len, DIM)
    assert f"f32[{','.join(map(str, buffer_shape))}]" in hlo
    assert _coordinate_gathers(hlo, buffer_shape) == []
    assert "read_unlock" in hlo          # its ops keep the reader's scope


def test_drop_mask_is_drawn_once_for_its_three_products(topo, tpu_backend):
    """The unlock branch masks g, g0 and gf with one p-wide Bernoulli draw,
    so exactly one p-wide fusion under the drop-mask scope computes the
    draw's hash (threefry's ``xor``s). Left to itself, XLA on a TPU fuses
    the draw into each product of a one-row group and draws it three times
    a step."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    hlo = _compile("asysvrg", one_chip, one_chip, specs=[
        SweepSpec(scheme="unlock", step_size=1.0, tau=9,
                  num_threads=10)]).as_text()
    bodies = dict(re.findall(r"^(%\S+) .*?\{\n(.*?)\n\}", hlo,
                             re.MULTILINE | re.DOTALL))
    drawing = [call for line in hlo.splitlines()
               if f",{DIM}]" in line.partition(" fusion(")[0]
               and f"/{DROP_MASK_SCOPE}/" in line
               for call in re.findall(r"calls=(%[\w.\-]+)", line)
               if " xor(" in bodies.get(call, "")]
    assert len(drawing) == 1, drawing


@pytest.mark.parametrize("algo", ["asysvrg", "hogwild", "svrg"])
def test_tpu_backend_plans_every_algo_on_the_compiled_kernel(tpu_backend,
                                                            algo):
    """On a TPU backend every algo plans, and each group's runner is keyed
    on the compiled `svrg_update` kernel, never the interpreter or the
    jnp reference."""
    from repro.service.cache import runner_key
    obj = LogisticRegression(np.ones((8, 4), np.float32),
                             np.ones(8, np.float32))
    specs = [SweepSpec(algo=algo, scheme=s, inner_steps=4,
                       tau=-1 if algo == "hogwild" else 0)
             for s in ("consistent", "inconsistent", "unlock")]
    plan = plan_sweep(obj, 1, specs)
    assert {k.engine for k in plan.groups} == {
        "hogwild" if algo == "hogwild" else "asysvrg"}
    for key in plan.groups:
        rkey = runner_key(key.engine, group_epochs=plan.group_epochs(key),
                          total=key.total, option=key.option,
                          buf_len=key.buf_len, drop_prob=0.02, mesh=None,
                          obj=obj)
        assert rkey[-1] == "compiled"


def test_interpret_override_on_tpu_raises(tpu_backend, monkeypatch):
    """$REPRO_KERNEL_MODE=interpret would hide the chip; 'reference' stays
    available on TPU to measure the kernel against its oracle."""
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "interpret")
    with pytest.raises(ValueError, match="interpret"):
        dispatch.kernel_mode()
    monkeypatch.setenv(dispatch.KERNEL_MODE_ENV, "reference")
    assert dispatch.kernel_mode() == "reference"
