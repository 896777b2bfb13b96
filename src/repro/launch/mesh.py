"""Mesh construction over the devices present (functions only —
importing this module never touches jax device state)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(n_data: int, n_model: int, *, n_pod: int = 1):
    """(data, model) mesh — (pod, data, model) when ``n_pod > 1`` — over
    the first ``n_pod * n_data * n_model`` devices present; raises when
    fewer are present. Every axis is Auto: `parallel/sharding.py`
    places activations with `with_sharding_constraint`, which refers
    only to Auto axes. The pod axis composes with data for batch
    sharding so the lowest-bandwidth axis only carries gradient
    reduce-scatter traffic (DESIGN.md §4)."""
    shape, axes = (n_data, n_model), ("data", "model")
    if n_pod > 1:
        shape, axes = (n_pod,) + shape, ("pod",) + axes
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def host_mesh(n_model: int | None = None):
    """(data, model) mesh over every device present: ``n_model`` devices
    per model group (all of them when None), the rest on data."""
    n = len(jax.devices())
    n_model = n if n_model is None else n_model
    if n_model < 1 or n % n_model:
        raise ValueError(
            f"model axis of {n_model} does not divide the {n} devices "
            f"present")
    return make_mesh(n // n_model, n_model)


def dp_axes_for_mesh(mesh) -> tuple[str, ...]:
    """The data-parallel (super)axis of our standard meshes: pod+data
    when a pod axis exists, else data — the tuple feeds
    ``RunConfig.dp_axis_name`` and ``ShardingRules.dp_axes`` alike."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def rules_for_mesh(mesh, *, strategy: str = "megatron", **kw):
    from repro.parallel.sharding import ShardingRules
    return ShardingRules(mesh=mesh, dp_axes=dp_axes_for_mesh(mesh),
                         strategy=strategy, **kw)
