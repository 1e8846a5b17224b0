"""Shared kernel-runtime policy: one backend check, one `use_kernels`
contract.

Every kernel package's public wrapper (``kernels/*/ops.py``) dispatches
the same way — compiled Pallas on TPU backends, interpret mode elsewhere
— and ``HyTMConfig.use_kernels``'s ``"auto"`` mode consults the *same*
backend check, so a backend-detection fix lands exactly once.  (The six
wrappers used to carry copy-pasted private ``_on_tpu`` helpers; any fix
had to be applied in six places and the copies could drift.)

The ``use_kernels`` tri-state:

* ``"auto"`` (default) — kernels on iff the default backend is TPU: the
  compiled Pallas path is where the raw speed lives (GraphCage-style
  tiled kernels), while on CPU/GPU backends interpret mode would only
  add overhead to the pure-JAX oracles.
* ``True``  — force the kernel path (interpret mode off-TPU): the
  equivalence tests and the CI roofline gate run the real kernel bodies
  on CPU this way.
* ``False`` — force the pure-JAX oracle engines.
"""

from __future__ import annotations

from collections.abc import Callable

import jax


def on_tpu() -> bool:
    """True when the default jax backend is a TPU — the one place the
    kernel wrappers and ``use_kernels="auto"`` check the backend."""
    return jax.default_backend() == "tpu"


def interpret_mode() -> bool:
    """Pallas ``interpret=`` default for the current backend."""
    return not on_tpu()


def resolve_use_kernels(setting: bool | str) -> bool:
    """Resolve ``HyTMConfig.use_kernels`` to a concrete (trace-time) bool.

    ``"auto"`` -> :func:`on_tpu`; booleans pass through.  Raises on any
    other string so a typo ('atuo') cannot silently disable the kernels.
    """
    if isinstance(setting, str):
        if setting != "auto":
            raise ValueError(
                f"use_kernels must be True, False, or 'auto', got {setting!r}")
        return on_tpu()
    return bool(setting)


def map_over_lanes(kernel: Callable) -> Callable:
    """``kernel`` (array arguments only) with a ``vmap`` rule that loops
    it over the batch axis (``lax.map``) instead of widening its grid.

    The service lanes ``vmap`` the engines, and Pallas's own batching
    rule gives every operand a batch block — which Mosaic refuses for a
    whole-array ``pl.ANY`` (HBM) operand, the DMA source or destination
    of ``hyb_gather`` and ``frontier_compact``.  One lane per call keeps
    the compiled kernel exactly the unbatched one.
    """
    wrapped = jax.custom_batching.custom_vmap(kernel)

    @wrapped.def_vmap
    def _rule(axis_size, in_batched, *args):
        del axis_size

        def one(lane_args):
            lane = iter(lane_args)
            return wrapped(*(next(lane) if b else a
                             for a, b in zip(args, in_batched)))

        out = jax.lax.map(one, [a for a, b in zip(args, in_batched) if b])
        return out, jax.tree.map(lambda _: True, out)

    return wrapped
