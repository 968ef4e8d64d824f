"""Jittable SmartConf controller — the paper's technique as a composable JAX
module (DESIGN.md §2).

The host-side ``SmartController`` cannot live inside a jitted serving or
training loop, so this module provides a functional twin:

  * :class:`ControllerSpec` / :class:`ControllerState` are array pytrees
    (vmap-/scan-/shard_map-compatible).
  * :func:`controller_step` is Eq. 2 + the two-pole hard-goal switch, built
    from ``jnp.where`` (branchless, so it vectorizes across controllers).
  * :func:`coordinated_step` implements §5.4's interaction protocol for a
    *batch* of controllers sharing metrics: N is recomputed on the fly from
    the metric ids, so adding/removing controllers needs no re-synthesis.
  * :func:`sharded_coordinated_step` runs controllers distributed over a mesh
    axis with ``jax.lax.psum`` computing the interaction counts — the paper's
    cross-module coordination mapped onto a TPU collective.

Everything here is pure; state threading is the caller's business (typically a
``lax.scan`` carry inside the serve loop, see ``serve/engine.py``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .controller import GoalSpec, ControllerModel, compute_pole, compute_virtual_goal

__all__ = [
    "ControllerSpec",
    "ControllerState",
    "make_spec",
    "init_state",
    "controller_step",
    "indirect_controller_step",
    "interaction_counts",
    "coordinated_step",
    "sharded_coordinated_step",
]


class ControllerSpec(NamedTuple):
    """Static-per-controller parameters, stored as arrays so a batch of
    heterogeneous controllers is just a stacked spec."""

    alpha: jax.Array          # Eq. 1 slope
    pole: jax.Array           # regular pole (§5.1)
    goal: jax.Array           # user goal value
    virtual_goal: jax.Array   # (1 - lambda) * goal for hard upper goals (§5.2)
    hard: jax.Array           # bool: two-pole mode enabled
    direction: jax.Array      # +1: metric must stay below goal; -1: above
    conf_min: jax.Array
    conf_max: jax.Array
    metric_id: jax.Array      # int32 id of the controlled metric (§5.4)
    super_hard: jax.Array     # bool: split gain across interacting controllers


class ControllerState(NamedTuple):
    conf: jax.Array


def make_spec(model: ControllerModel, goal: GoalSpec, *, metric_id: int = 0) -> ControllerSpec:
    """Build a single controller spec from the host-side synthesis artifacts."""
    direction = 1.0 if goal.direction == "upper" else -1.0
    return ControllerSpec(
        alpha=jnp.asarray(model.alpha, jnp.float32),
        pole=jnp.asarray(compute_pole(model.delta), jnp.float32),
        goal=jnp.asarray(goal.value, jnp.float32),
        virtual_goal=jnp.asarray(compute_virtual_goal(goal, model.lam), jnp.float32),
        hard=jnp.asarray(goal.hard),
        direction=jnp.asarray(direction, jnp.float32),
        conf_min=jnp.asarray(model.conf_min, jnp.float32),
        conf_max=jnp.asarray(min(model.conf_max, 3.4e38), jnp.float32),
        metric_id=jnp.asarray(metric_id, jnp.int32),
        super_hard=jnp.asarray(goal.super_hard),
    )


def stack_specs(specs: list[ControllerSpec]) -> ControllerSpec:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *specs)


def init_state(initial_conf) -> ControllerState:
    return ControllerState(conf=jnp.asarray(initial_conf, jnp.float32))


def _next_conf(spec: ControllerSpec, base: jax.Array, measurement: jax.Array,
               n_interacting: jax.Array) -> jax.Array:
    """Eq. 2 with the §5.2 context-aware pole and §5.4 interaction factor."""
    measurement = measurement.astype(jnp.float32)
    # danger: metric crossed the virtual goal on the unsafe side.
    danger = jnp.where(spec.direction > 0,
                       measurement > spec.virtual_goal,
                       measurement < spec.virtual_goal)
    pole = jnp.where(spec.hard & danger, jnp.zeros_like(spec.pole), spec.pole)
    error = spec.virtual_goal - measurement
    n = jnp.where(spec.super_hard, n_interacting.astype(jnp.float32), 1.0)
    gain = (1.0 - pole) / (spec.alpha * n)
    nxt = base + gain * error
    return jnp.clip(nxt, spec.conf_min, spec.conf_max)


def controller_step(spec: ControllerSpec, state: ControllerState,
                    measurement: jax.Array) -> tuple[ControllerState, jax.Array]:
    """One control interval for a direct configuration."""
    conf = _next_conf(spec, state.conf, measurement, jnp.asarray(1.0))
    return ControllerState(conf=conf), conf


def indirect_controller_step(spec: ControllerSpec, state: ControllerState,
                             measurement: jax.Array, deputy: jax.Array
                             ) -> tuple[ControllerState, jax.Array]:
    """One control interval for an indirect configuration (§5.3): Eq. 2
    integrates from the *deputy's* actual value.  The returned value is the
    desired deputy value; the caller applies its transducer (host- or
    graph-side) to obtain the threshold configuration."""
    conf = _next_conf(spec, deputy.astype(jnp.float32), measurement, jnp.asarray(1.0))
    return ControllerState(conf=conf), conf


def interaction_counts(metric_ids: jax.Array, num_metrics: int) -> jax.Array:
    """N per controller: how many controllers share each controller's metric."""
    onehot = jax.nn.one_hot(metric_ids, num_metrics, dtype=jnp.float32)  # [C, M]
    per_metric = onehot.sum(axis=0)                                      # [M]
    return onehot @ per_metric                                           # [C]


def coordinated_step(specs: ControllerSpec, states: ControllerState,
                     measurements: jax.Array, *, num_metrics: int = 8
                     ) -> tuple[ControllerState, jax.Array]:
    """Batched controllers with §5.4 coordination (single device / vmapped).

    ``specs``/``states`` hold stacked arrays of C controllers; controllers with
    equal ``metric_id`` and ``super_hard`` split the error N ways."""
    n = interaction_counts(specs.metric_id, num_metrics)
    conf = _next_conf(specs, states.conf, measurements, n)
    return ControllerState(conf=conf), conf


def sharded_coordinated_step(mesh, axis_name: str, *, num_metrics: int = 8):
    """§5.4 coordination across a mesh axis.

    Returns a shard_mapped function ``(specs, states, measurements) ->
    (states', confs)`` where each shard owns a slice of the controller batch
    and the interaction count N is agreed globally via ``lax.psum`` — i.e. the
    paper's "controllers independently work together" protocol expressed as a
    TPU collective.  Controllers for different modules/pods never need to
    rendezvous at a single code location (the paper's §5.4 infeasibility
    argument); they only share this metric-count reduction.
    """

    def local_step(specs: ControllerSpec, states: ControllerState,
                   measurements: jax.Array):
        onehot = jax.nn.one_hot(specs.metric_id, num_metrics, dtype=jnp.float32)
        per_metric = jax.lax.psum(onehot.sum(axis=0), axis_name)  # global counts
        n = onehot @ per_metric
        conf = _next_conf(specs, states.conf, measurements, n)
        return ControllerState(conf=conf), conf

    spec_p = ControllerSpec(*(P(axis_name) for _ in ControllerSpec._fields))
    state_p = ControllerState(P(axis_name))
    return jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(spec_p, state_p, P(axis_name)),
        out_specs=(state_p, P(axis_name)),
    )
