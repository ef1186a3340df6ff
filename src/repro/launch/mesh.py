"""Production mesh construction.

Single pod = 16x16 v5e chips: ``model`` = 16-way tensor parallel within a
replica, ``data`` = 16 replicas per pod (SYMPHONY's load-balancing domain).
Multi-pod adds a leading ``pod`` axis (DCN-connected).

Defined as functions so importing this module never touches jax device state
(`force_host_device_count` touches only ``os.environ`` and must run before
jax initializes its backends).
"""
from __future__ import annotations

import os

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def force_host_device_count(n: int) -> bool:
    """Append ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``,
    PRESERVING any flags the user already set.  An existing forced count
    (user-chosen device topology) is respected, not overwritten.  Must run
    before jax initializes its backends — a no-op afterwards, so a
    multi-device CPU bench sets ``XLA_FLAGS`` before it starts.  Returns
    whether the flag is (now) present."""
    cur = os.environ.get("XLA_FLAGS", "")
    if _FORCE_FLAG in cur:
        return True
    os.environ["XLA_FLAGS"] = f"{cur} {_FORCE_FLAG}={n}".strip()
    return True


def make_production_mesh(*, multi_pod: bool = False):
    import jax
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_serving_mesh(tp: int = 1):
    """The serving node's device mesh: a 1-D ``("model",)`` mesh of ``tp``
    devices — one node = ``tp`` accelerators serving one model replica
    (`RealBackend(mesh=...)` shards the stacked KV pools and block weights
    over it).  Data parallelism across replicas is the cluster scheduler's
    job (one engine per replica), so the serving mesh carries no ``data``
    axis.  On CPU, call `force_host_device_count` before importing jax (or
    set ``XLA_FLAGS``) to get the virtual devices."""
    import jax
    if tp > jax.device_count():
        raise ValueError(
            f"make_serving_mesh(tp={tp}): only {jax.device_count()} devices "
            f"visible — on CPU, force host devices via XLA_FLAGS "
            f"({_FORCE_FLAG}=N) before jax initializes")
    return jax.make_mesh((tp,), ("model",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def make_debug_mesh(data: int = 1, model: int = 1):
    """Tiny mesh for CPU tests (requires that many host devices)."""
    import jax
    return jax.make_mesh(
        (data, model), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def dp_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def mp_axis(mesh) -> str:
    return "model"
