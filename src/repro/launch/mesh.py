"""Production mesh construction.

A function (not a module-level constant) so importing this module never
touches jax device state — required because the dry-run must set
XLA_FLAGS before any jax initialization.  Every mesh is Auto-typed.
"""

from __future__ import annotations

import jax


def _mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips/pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(n_data: int = 2, n_model: int = 2, pods: int = 0):
    """Small mesh over however many (possibly fake) devices exist — used by
    the distributed-correctness tests."""
    if pods:
        shape, axes = (pods, n_data, n_model), ("pod", "data", "model")
    else:
        shape, axes = (n_data, n_model), ("data", "model")
    return _mesh(shape, axes)


def make_graph_mesh(n_devices: int | None = None, axis: str = "graph"):
    """1-D mesh for the sharded HyTM sweep (repro.dist.graph_shard): the
    partition edge blocks shard over ``axis``.  Defaults to every visible
    device (forced-host devices included)."""
    if n_devices is None:
        n_devices = len(jax.devices())
    return _mesh((n_devices,), (axis,))


def forced_host_device_env(n_devices: int) -> dict:
    """Environment for a subprocess that must see ``n_devices`` fake CPU
    devices — jax locks the device count at first backend init, so
    multi-device tests and benchmarks re-exec with this env instead of
    reconfiguring the parent.  The single definition of the recipe
    (``tests/_forced_devices.py`` and the device-sweep benchmarks both
    build on it), so an environment change lands in one place:

    * ``XLA_FLAGS=--xla_force_host_platform_device_count=N``;
    * ``JAX_PLATFORMS=cpu`` — forced counts only exist on the CPU
      backend; without the pin, a machine with an accelerator would run
      everything on 1 real device;
    * ``PYTHONPATH`` led by this checkout's ``src`` (derived from the
      installed ``repro`` package, so it works from any cwd).

    These children are CPU rehearsals.  A parent whose backend is an
    accelerator refuses: it already holds the chip, and its children
    would report forced-host CPU numbers under the benchmark's names.
    """
    import os

    import repro

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"forced-host CPU devices are a CPU rehearsal; this process runs "
            f"on {jax.default_backend()!r} and holds its devices, and the "
            f"children would time the CPU under the benchmark's names — run "
            f"the multi-device path in this process (chip_smoke.py "
            f"--four-chips) instead")

    # repro is a namespace package (no __init__.py): locate src via
    # __path__, not __file__ (which is None)
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    prior = env.get("PYTHONPATH")
    env["PYTHONPATH"] = src + (os.pathsep + prior if prior else "")
    return env
