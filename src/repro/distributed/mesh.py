"""Production mesh construction.  A FUNCTION (not a module-level constant) so
importing this module never touches jax device state."""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def _make_mesh(shape, axes, devices):
    """``jax.make_mesh`` with every axis ``Auto``: the engine and the train
    step place arrays with ``NamedSharding``/``with_sharding_constraint`` and
    reshape sharded arrays eagerly, which ``Explicit`` axes (the default
    since jax 0.7) reject."""
    return jax.make_mesh(shape, axes, (AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count or run on "
            "real hardware")
    return _make_mesh(shape, axes, devices[:n])


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh for CPU tests (requires >= prod(shape) host devices)."""
    n = math.prod(shape)
    return _make_mesh(shape, axes, jax.devices()[:n])


# ---------------------------------------------------------------------------
# sketch-shard placement (StepSpec.shards — see kernels/sketch_merge.py)
# ---------------------------------------------------------------------------

def _shard_mesh_size(n_shards: int, n_devices: int) -> int:
    """Devices a ``("shard",)`` mesh uses for ``n_shards`` shards: the
    largest DIVISOR of ``n_shards`` that fits the available devices, so the
    shard-major delta arrays partition evenly along the mesh axis (shards
    are a power of two, so this is the largest power of two <= both)."""
    assert n_shards >= 1 and n_devices >= 1
    n = min(n_shards, n_devices)
    while n_shards % n:
        n -= 1
    return n


def shard_placement(n_shards: int, devices=None) -> list:
    """Shard -> device placement map for the sharded frequency sketch.

    Shard ``s`` owns the ``width/n_shards`` counter slice ``s`` of the
    sketch buffers' delta halves plus its slice of the replicated global
    estimate; per-access writes are shard-local, and the once-per-epoch
    ``merge_halve`` fold is the only cross-device state exchange (an
    all-gather that refreshes every device's global replica).

    BLOCK placement: with ``D`` mesh devices (``_shard_mesh_size`` — the
    largest divisor of ``n_shards`` that fits), device ``d`` owns the
    ``n_shards/D`` consecutive shards ``[d*S/D, (d+1)*S/D)``.  This is
    exactly how ``jax.sharding.NamedSharding``/``shard_map`` split axis 0
    of the shard-major delta arrays over :func:`make_shard_mesh`, so this
    map, the mesh runner (``core.device_simulate`` ``DeviceWTinyLFU``
    ``(mesh=)``), and a sharding-visualizer all describe the same
    placement.  (It used to be round-robin, which contradicted the mesh's
    contiguous split whenever ``n_shards > n_devices`` — ISSUE 5.)
    The single-host simulation is the n_devices=1 special case.
    """
    assert n_shards >= 1
    devices = list(jax.devices()) if devices is None else list(devices)
    assert devices, "shard placement needs at least one device"
    n = _shard_mesh_size(n_shards, len(devices))
    per = n_shards // n
    return [devices[s // per] for s in range(n_shards)]


def make_shard_mesh(n_shards: int, devices=None, require: int = 0):
    """1-D ``("shard",)`` mesh for the multi-device sharded-sketch run
    (``core.device_simulate.simulate_trace(..., shards=S, mesh=...)``): the
    delta arrays are partitioned along axis 0 (``NamedSharding``/
    ``shard_map``), so the mesh takes the largest divisor of ``n_shards``
    that the available devices can host — device ``d`` then owns the
    contiguous shard block ``[d*S/D, (d+1)*S/D)``, consistent with
    :func:`shard_placement`.

    ``require=D`` demands a mesh of exactly D devices and raises an eager
    ``ValueError`` when the machine cannot host it — instead of silently
    shrinking to what fits (the default, which is right for portable
    scripts but wrong for placement tests and fault drills that NEED the
    multi-device layout)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if require:
        if require > len(devices):
            raise ValueError(
                f"make_shard_mesh(require={require}) but only "
                f"{len(devices)} device(s) are available — set "
                "XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{require} (before importing jax) or run on hardware "
                "with enough devices")
        if n_shards % require:
            raise ValueError(
                f"make_shard_mesh(require={require}): {n_shards} shards "
                "do not split evenly (block placement needs "
                "shards % devices == 0)")
        return _make_mesh((require,), ("shard",), devices[:require])
    n = _shard_mesh_size(max(1, n_shards), len(devices))
    return _make_mesh((n,), ("shard",), devices[:n])


def mesh_state_shardings(mesh, state_keys) -> dict:
    """NamedShardings that place a mesh-layout engine state pytree
    (``core.device_simulate`` keys) onto ``mesh``: the shard-major delta
    arrays split along ``("shard",)`` axis 0, everything else replicated.
    The elastic-restore path (``core.device_simulate.resume_trace``) uses
    this to ``jax.device_put`` a checkpoint restored from a DIFFERENT mesh
    size onto the current one."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return {k: NamedSharding(
        mesh, P("shard") if k in ("dcounters", "ddoorkeeper") else P())
        for k in state_keys}
