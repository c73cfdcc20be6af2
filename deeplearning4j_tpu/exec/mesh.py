"""Mesh construction for the execution core (docs/SHARDING.md).

One place decides what the device mesh looks like; every compile site
(train step, ``fit_scan``, bucketed serving, incremental decode) builds
its ``NamedSharding`` specs against the SAME two named axes:

- ``data``  — batch / slot dimension shards here (pure DP by default);
- ``model`` — Megatron-style tensor parallelism (weight output/input
  dims); size 1 unless explicitly requested, so the default mesh is
  pure data-parallel over ``jax.devices()``.

Single-device processes get a 1x1 mesh and the executor collapses to a
plain ``jax.jit`` (the mesh=1 special case — zero new XLA programs, the
trace-count tests pin this).

The mesh can be shaped without code changes via ``DL4JTPU_MESH``:

    DL4JTPU_MESH=off            # force single-device execution
    DL4JTPU_MESH=data=4,model=2 # explicit axis sizes (product must
                                # divide the visible device count)
    DL4JTPU_MESH=model=2        # data axis absorbs the rest

CPU CI gets multiple devices by setting
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE jax
initializes; ``host_device_env`` composes that flag into a subprocess
environment without perturbing the current process (tests/conftest.py
``mesh8`` and the bench sharded rows use it).
"""

import os
from typing import Optional

import numpy as np
import jax
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"

_default_mesh: Optional[Mesh] = None


def build_mesh(devices=None, model_parallel: int = 1) -> Mesh:
    """A 2-D ``(data, model)`` mesh over ``devices`` (default: all).

    ``model_parallel`` must divide the device count; the data axis
    absorbs the rest.
    """
    devs = list(jax.devices()) if devices is None else list(devices)
    m = max(1, int(model_parallel))
    if len(devs) % m:
        raise ValueError(
            f"model_parallel={m} does not divide {len(devs)} devices")
    return Mesh(np.array(devs).reshape(len(devs) // m, m),
                (DATA_AXIS, MODEL_AXIS))


def _publish_gauges(mesh: Mesh) -> None:
    from deeplearning4j_tpu.monitor.metrics import get_registry
    reg = get_registry()
    reg.gauge(
        "dl4jtpu_mesh_devices",
        "Devices in the execution mesh (1 = single-device special case)."
    ).set(mesh.size)
    ax = reg.gauge(
        "dl4jtpu_mesh_axis_size",
        "Size of each named mesh axis (batch shards over 'data', "
        "Megatron TP over 'model').", ("axis",))
    for name in mesh.axis_names:
        ax.labels(axis=name).set(mesh.shape[name])


def _mesh_from_env(spec: str) -> Mesh:
    spec = spec.strip().lower()
    if spec in ("off", "1", "single", "none"):
        return build_mesh(jax.devices()[:1])
    sizes = {}
    for part in spec.split(","):
        if not part.strip():
            continue
        k, _, v = part.partition("=")
        sizes[k.strip()] = int(v)
    n = len(jax.devices())
    model = sizes.get(MODEL_AXIS, 1)
    data = sizes.get(DATA_AXIS, max(1, n // max(1, model)))
    want = data * model
    if want > n or n % want:
        raise ValueError(
            f"DL4JTPU_MESH={spec!r} needs {want} devices, have {n}")
    return build_mesh(jax.devices()[:want], model_parallel=model)


def default_mesh() -> Mesh:
    """The process-wide mesh: all visible devices, pure DP, unless
    ``DL4JTPU_MESH`` or ``set_default_mesh`` says otherwise."""
    global _default_mesh
    if _default_mesh is None:
        env = os.environ.get("DL4JTPU_MESH", "").strip()
        _default_mesh = _mesh_from_env(env) if env else build_mesh()
        _publish_gauges(_default_mesh)
    return _default_mesh


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    """Override (or with None, reset) the process default mesh. Drops
    the cached default executor so the next compile sees the new mesh;
    programs already compiled keep their old placement."""
    global _default_mesh
    _default_mesh = mesh
    if mesh is not None:
        _publish_gauges(mesh)
    from deeplearning4j_tpu.exec import executor as _ex
    _ex._invalidate_default()


def device_info() -> dict:
    """The device this process computes on, as JAX reports it — what a
    ready line, ``/stats`` or a result names so that no figure is read as
    coming from a device it did not run on."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def host_device_env(n: int = 8, base=None) -> dict:
    """Environment for a SUBPROCESS that should see ``n`` virtual CPU
    devices. The host-device-count flag only takes effect before jax
    initializes, so it cannot be flipped in-process — composing it into
    a child environment is the subprocess-safe way (the parent's device
    state is untouched).

    The child is PINNED TO THE CPU (``JAX_PLATFORMS=cpu``): an
    accelerator belongs to one process at a time, and a parent that has
    touched JAX holds it. Nothing such a child times is a chip figure."""
    env = dict(os.environ if base is None else base)
    flags = [t for t in env.get("XLA_FLAGS", "").split()
             if not t.startswith(_HOST_COUNT_FLAG)]
    flags.append(f"{_HOST_COUNT_FLAG}={int(n)}")
    env["XLA_FLAGS"] = " ".join(flags)
    env["JAX_PLATFORMS"] = "cpu"
    return env
