"""Production mesh construction.

A FUNCTION, not a module constant, so importing this module never
touches jax device state (device count is locked at first jax init —
dryrun.py sets XLA_FLAGS before any import for that reason).

Mesh layout (TPU v5e pods, 256 chips each):
  single-pod : (16, 16)      axes ("data", "model")
  multi-pod  : (2, 16, 16)   axes ("pod", "data", "model")

Axis roles under the baseline HDArray rules (train/sharding.py):
  pod    — pure data parallel across pods (grad all-reduce crosses DCI)
  data   — data parallel + FSDP param sharding (ZeRO within a pod)
  model  — tensor parallel (heads/ffn/vocab) + expert parallel (MoE) +
           sequence parallel for long-context decode
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto(axes) -> dict:
    """``axis_types`` for ``jax.make_mesh``: every axis Auto.  The
    sharding code places arrays with ``NamedSharding`` and
    ``with_sharding_constraint`` (GSPMD propagation), which Explicit
    axes — ``jax.make_mesh``'s default — reject."""
    return {"axis_types": (AxisType.Auto,) * len(axes)}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — "
            "run via launch/dryrun.py, which forces "
            "--xla_force_host_platform_device_count=512")
    return jax.make_mesh(shape, axes, devices=devices[:n], **_auto(axes))


def make_debug_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over however many host devices exist (tests)."""
    n = 1
    for s in shape:
        n *= s
    return jax.make_mesh(shape, axes, devices=jax.devices()[:n],
                         **_auto(axes))


# ----------------------------------------------------------------------
# HDArray executor-layer host meshes
# ----------------------------------------------------------------------
def ensure_host_devices(n: int) -> bool:
    """Request at least `n` XLA host-platform devices (JaxExecutor).

    Must run BEFORE jax's first backend init (the device count is
    locked then).  A pre-existing ``xla_force_host_platform_device_
    count`` smaller than `n` is raised to `n`.  Returns True when `n`
    devices are (or will be) available, False when jax has already
    initialized with fewer — callers fall back or get the clear error
    from :func:`make_host_mesh`.
    """
    import os
    import re
    import sys

    key = "xla_force_host_platform_device_count"
    flags = os.environ.get("XLA_FLAGS", "")
    m = re.search(key + r"=(\d+)", flags)
    if m is None:
        os.environ["XLA_FLAGS"] = (
            flags + f" --{key}={n}").strip()
    elif int(m.group(1)) < n:
        os.environ["XLA_FLAGS"] = re.sub(key + r"=\d+", f"{key}={n}", flags)
    if "jax" in sys.modules:
        import jax as _jax

        # if the backend was not initialized yet, the env var above is
        # still effective and this reports the post-flag device count
        return len(_jax.devices()) >= n
    return True


def make_host_mesh(nproc: int, axis: str = "p"):
    """1-D mesh of `nproc` host devices — the device fabric the
    JaxExecutor lowers classified CommPlans onto (one mesh rank per
    HDArray process)."""
    devices = jax.devices()
    if len(devices) < nproc:
        raise RuntimeError(
            f"host mesh needs {nproc} devices, found {len(devices)} — "
            "set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{nproc} before the first jax init (see "
            "launch.mesh.ensure_host_devices)")
    return jax.make_mesh((nproc,), (axis,), devices=devices[:nproc],
                         **_auto((axis,)))
