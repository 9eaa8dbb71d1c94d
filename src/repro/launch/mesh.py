"""Production meshes.  A function (not a module constant) so importing this
module never touches jax device state."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...], devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.  jax's own default is
    ``Explicit``, under which the sharding-in-types checks reject the
    model's plain gathers (``embed[tokens]``); every mesh the model runs on
    comes from here."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Whatever devices exist locally, as a (1, n) (data, model) mesh."""
    return make_mesh((1, len(jax.devices())), ("data", "model"))


def make_local_mesh(d: int, m: int):
    """A (data, model) mesh over the first d*m local devices (serve --mesh,
    dryrun --quick; on CPU force host devices via XLA_FLAGS first)."""
    devs = jax.devices()
    if d * m > len(devs):
        raise ValueError(
            f"mesh {d}x{m} needs {d * m} devices, have {len(devs)} "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return make_mesh((d, m), ("data", "model"), devices=devs[: d * m])
