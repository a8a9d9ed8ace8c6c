"""Production mesh factory.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax init,
and smoke tests must keep seeing 1 CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes, axis_names):
    """``jax.make_mesh`` with every axis ``Auto``: the sweep's ``shard_map``
    and the sharding rules place arrays explicitly, never through types."""
    return jax.make_mesh(axis_shapes, axis_names,
                         axis_types=(AxisType.Auto,) * len(axis_names))


def make_production_mesh(*, multi_pod: bool = False):
    """(16,16)=(data,model) single pod (256 chips) or
    (2,16,16)=(pod,data,model) for 2 pods (512 chips).

    The same axis names scale to N pods — the `pod` axis composes with
    `data` in the sharding rules (see repro/sharding/rules.py), so a
    (8,16,16) 2048-chip mesh needs no model-code changes."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh with the production axis names (CPU smoke tests)."""
    return make_mesh((1, 1), ("data", "model"))


_SWEEP_MESHES: dict = {}


def make_sweep_mesh(num_devices: int | None = None):
    """1-D (`data`,) mesh over the host's devices, for config-row sharding.

    `repro.core.sweep.run_sweep` shards each group's config-batch axis over
    the `data` axis of whatever mesh it is given (or the ambient
    `mesh_context` mesh); this factory builds the simplest such mesh — all
    local devices on one axis. CI's forced-8-device CPU job and the sharded
    bench smoke both use it; on real hardware pass `make_production_mesh()`
    instead (same axis name, pod-scale device set).

    Memoized per device count: repeated calls (one per service flush, say)
    return the SAME Mesh object, and `sharding.context.mesh_fingerprint`
    additionally makes distinct-but-equal meshes share compiled-runner
    cache entries.
    """
    n = num_devices or len(jax.devices())
    mesh = _SWEEP_MESHES.get(n)
    if mesh is None:
        mesh = _SWEEP_MESHES[n] = make_mesh((n,), ("data",))
    return mesh
