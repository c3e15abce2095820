"""Pipeline parallelism over a ``pipe`` mesh axis.

No reference analog (SURVEY.md §2.5 — BigDL is DP-only). This is the
standard TPU GPipe-style schedule expressed with ``shard_map`` +
``ppermute``: each device along the pipe axis owns one stage's weights
(a homogeneous stacked-layer pytree sharded on its leading axis), and
microbatch activations flow around the ring, one neighbor hop per tick.
``n_micro + n_stages - 1`` ticks drain the pipeline; bubble fraction
``(n_stages-1)/(n_micro+n_stages-1)``.

Constraint (standard for TPU pipelining): stages must be *homogeneous* —
same apply function and same param structure per stage (e.g. transformer
blocks) so stage params stack on a leading axis that shards over ``pipe``.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_stage_fn(stage_apply: Callable, axis_name: str = "pipe"):
    """Build the per-device pipeline body.

    ``stage_apply(stage_params, x) -> y`` maps one microbatch through one
    stage; activations keep a constant shape across stages.

    Returns ``run(stage_params, microbatches)`` for use inside shard_map:
    - ``stage_params``: this device's stage params (leading stage axis of
      size 1 already squeezed by the in_spec).
    - ``microbatches``: (n_micro, mb, ...) — full microbatch stack,
      replicated; only stage 0 reads it.
    Output: (n_micro, mb, ...) final-stage results (valid on the last
    stage; zeros elsewhere — the wrapper's out_spec picks the last stage).
    """

    def run(stage_params, microbatches):
        n_stages = lax.axis_size(axis_name)
        idx = lax.axis_index(axis_name)
        n_micro = microbatches.shape[0]
        ticks = n_micro + n_stages - 1
        from bigdl_tpu.parallel.ring_attention import _varying
        like = jax.tree_util.tree_leaves(stage_params)[0]
        state = _varying(jnp.zeros_like(microbatches[0]), like)
        outputs = _varying(jnp.zeros_like(microbatches), like)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (clamped; masked by validity)
            feed = lax.dynamic_index_in_dim(
                microbatches, jnp.clip(t, 0, n_micro - 1), 0,
                keepdims=False)
            x = jnp.where(idx == 0, feed, state)
            y = stage_apply(stage_params, x)
            # last stage stores result for microbatch t-(n_stages-1)
            out_t = t - (n_stages - 1)
            valid = (idx == n_stages - 1) & (out_t >= 0)
            outputs = lax.cond(
                valid,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_t, 0, n_micro - 1), 0),
                lambda o: o, outputs)
            # activations hop to the next stage (ICI neighbor)
            perm = [(j, (j + 1) % n_stages) for j in range(n_stages)]
            state = lax.ppermute(y, axis_name, perm)
            return (state, outputs), None

        (state, outputs), _ = lax.scan(
            tick, (state, outputs), jnp.arange(ticks))
        # only the last stage wrote real values (others hold zeros), so the
        # psum reduces to "broadcast the last stage's buffer" and lets the
        # wrapper emit a replicated (n_micro, mb, ...) output
        return lax.psum(outputs, axis_name)

    return run


class PipelineModule:
    """Functional pipeline executor over stacked homogeneous stages.

    ``stage_apply(stage_params, x) -> y``; ``stacked_params`` is a pytree
    whose leaves have leading dim ``n_stages``, sharded over ``pipe``.
    """

    def __init__(self, stage_apply: Callable, n_stages: int,
                 mesh: Mesh, axis: str = "pipe", remat: bool = False):
        if axis not in mesh.axis_names:
            raise ValueError(f"mesh has no axis {axis!r}")
        if mesh.shape[axis] != n_stages:
            raise ValueError(
                f"mesh axis {axis}={mesh.shape[axis]} != n_stages {n_stages}")
        self.mesh = mesh
        self.axis = axis
        self.n_stages = n_stages
        if remat:
            # recompute stage activations in the backward schedule instead
            # of storing every tick's outputs (GPipe's activation memory
            # trade — jax.checkpoint is the XLA-native rematerialization)
            stage_apply = jax.checkpoint(stage_apply)
        body = pipeline_stage_fn(
            lambda p, x: stage_apply(
                jax.tree_util.tree_map(lambda l: l[0], p), x),
            axis_name=axis)
        self._fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis), P()),
            out_specs=P())

    def __call__(self, stacked_params, microbatches):
        """microbatches: (n_micro, mb, ...) -> (n_micro, mb, ...)."""
        return self._fn(stacked_params, jnp.asarray(microbatches))

    def place_params(self, stacked_params):
        """Shard stacked stage params over the pipe axis."""
        sh = NamedSharding(self.mesh, P(self.axis))
        return jax.tree_util.tree_map(
            lambda l: jax.device_put(l, sh), stacked_params)


def split_microbatches(batch, n_micro: int):
    """(B, ...) -> (n_micro, B/n_micro, ...) pytree-wise."""
    def split(a):
        b = a.shape[0]
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible by {n_micro}")
        return a.reshape((n_micro, b // n_micro) + a.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def make_pipeline_train_step(pipe: PipelineModule, loss_fn: Callable,
                             optim, lr: float):
    """Pipeline *training*: GPipe schedule with gradient accumulation.

    The forward schedule in :func:`pipeline_stage_fn` is pure jax (scan +
    ppermute + select), so reverse-mode autodiff through it IS the GPipe
    backward schedule: XLA transposes the scan into the reverse tick
    order, ppermutes flow the activation cotangents stage-to-stage the
    opposite way around the ring, and each stage's weight gradient
    accumulates over its microbatches inside the scan transpose — the
    hand-written backward ring of the GPU frameworks falls out of the
    program transform. Use ``PipelineModule(remat=True)`` to recompute
    activations in the backward pass instead of storing every tick.

    ``loss_fn(outputs, targets) -> scalar`` sees the full
    ``(n_micro, mb, ...)`` stacks (mean over both axes for the standard
    per-example mean loss).

    Returns ``step(stacked_params, opt_state, microbatches, targets) ->
    (new_params, new_opt_state, loss)``, jitted with donated state.
    """

    def step(stacked_params, opt_state, microbatches, micro_targets):
        def loss(p):
            outs = pipe(p, microbatches)
            return loss_fn(outs, micro_targets)

        l, grads = jax.value_and_grad(loss)(stacked_params)
        new_params, new_opt = optim.step(stacked_params, grads,
                                         opt_state, lr)
        return new_params, new_opt, l

    from bigdl_tpu import observability as obs
    return obs.compiled(step, name="parallel/pipeline_train_step",
                        donate_argnums=(0, 1))
