"""Distributed trainer: one global-view jit train step.

≙ Horovod's ``DistributedOptimizer`` + ``broadcast_global_variables``
(/root/reference/examples/horovod/tensorflow_mnist.py, SURVEY.md §2.5), made
TPU-native: instead of wrapping an optimizer with an explicit allreduce hook,
the step is compiled once over the whole mesh with the batch sharded along
(data, fsdp) and params laid out by the model's logical axes — XLA derives
the gradient reductions from the shardings and fuses them into the backward
pass (reduce-scatter/all-gather on ICI for fsdp, all-reduce for pure data).
The initial-broadcast problem disappears: params are initialized once,
globally, by a jitted init.

Works for stateless models (llama, mnist: ``loss_fn(params, batch)``) and
stateful ones (resnet: ``loss_fn(params, state, batch) -> (loss, new_state)``
via ``has_model_state=True``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from mpi_operator_tpu.parallel.sharding import (
    Rules,
    logical_spec,
    mesh_filtered_spec,
)


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    learning_rate: float = 1e-3
    warmup_steps: int = 0
    total_steps: int = 0  # 0 = constant lr after warmup
    weight_decay: float = 0.0
    beta1: float = 0.9
    beta2: float = 0.95
    grad_clip_norm: float = 1.0
    optimizer: str = "adamw"  # or "sgd", "momentum"
    momentum: float = 0.9


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    model_state: Any  # {} when the model is stateless


def _schedule(config: TrainerConfig) -> optax.Schedule:
    if config.warmup_steps == 0 and config.total_steps == 0:
        return optax.constant_schedule(config.learning_rate)
    if config.total_steps:
        return optax.warmup_cosine_decay_schedule(
            0.0, config.learning_rate, config.warmup_steps,
            max(config.total_steps, config.warmup_steps + 1),
        )
    return optax.linear_schedule(0.0, config.learning_rate, max(config.warmup_steps, 1))


def _optimizer(config: TrainerConfig) -> optax.GradientTransformation:
    sched = _schedule(config)
    if config.optimizer == "adamw":
        opt = optax.adamw(
            sched, b1=config.beta1, b2=config.beta2,
            weight_decay=config.weight_decay,
        )
    elif config.optimizer == "momentum":
        opt = optax.sgd(sched, momentum=config.momentum)
    elif config.optimizer == "sgd":
        opt = optax.sgd(sched)
    else:
        raise ValueError(f"unknown optimizer {config.optimizer!r}")
    if config.grad_clip_norm > 0:
        return optax.chain(optax.clip_by_global_norm(config.grad_clip_norm), opt)
    return opt


class Trainer:
    """Compiles and owns the sharded train step.

    Args:
      loss_fn: ``(params, batch) -> loss`` or, with ``has_model_state``,
        ``(params, model_state, batch) -> (loss, new_model_state)``. A
        stateless loss may return ``(loss, {name: scalar})``: named scalars
        of the step (a router's counters), which ride the step's metrics
        under their names.
      params_axes: logical-axes pytree matching params (models.*.logical_axes).
      mesh: the job mesh (runtime.mesh_from_context / build_mesh).
      model_state_axes: logical-axes pytree for model_state when stateful.
      batch_axes: logical axes for each batch leaf dim; default shards dim 0
        along (data, fsdp) — a per-leaf dict is accepted for ragged batches.
    """

    def __init__(
        self,
        loss_fn: Callable,
        params_axes: Any,
        mesh: Mesh,
        config: TrainerConfig = TrainerConfig(),
        *,
        has_model_state: bool = False,
        model_state_axes: Any = None,
        rules: Optional[Rules] = None,
        donate: bool = True,
    ):
        self.config = config
        self.mesh = mesh
        self.rules = rules
        self.has_model_state = has_model_state
        self.tx = _optimizer(config)
        self._loss_fn = loss_fn
        self._params_axes = params_axes
        self._model_state_axes = model_state_axes if has_model_state else {}
        self._step_fn = None
        self._donate = donate

    # -- shardings ---------------------------------------------------------

    def _sharding_of(self, axes_tree):
        return jax.tree.map(
            lambda axes: NamedSharding(
                self.mesh,
                mesh_filtered_spec(logical_spec(axes, self.rules), self.mesh),
            ),
            axes_tree,
            is_leaf=lambda x: isinstance(x, tuple),
        )

    def params_sharding(self):
        return self._sharding_of(self._params_axes)

    def model_state_sharding(self):
        return self._sharding_of(self._model_state_axes)

    def batch_sharding(self, batch):
        spec = mesh_filtered_spec(logical_spec(["batch"], self.rules), self.mesh)
        return jax.tree.map(lambda _: NamedSharding(self.mesh, spec), batch)

    def state_sharding(self, state: "TrainState") -> "TrainState":
        """Sharding pytree for a TrainState, derived from the shapes of
        ``state.params`` alone — so it can be named for a state that does
        not exist yet (``state`` may hold ``jax.ShapeDtypeStruct`` leaves)."""
        p_sh = self.params_sharding()
        return TrainState(
            step=NamedSharding(self.mesh, PartitionSpec()),
            params=p_sh,
            opt_state=self._opt_sharding_for(state.params, p_sh),
            model_state=self.model_state_sharding()
            if self.has_model_state
            else {},
        )

    def abstract_state(self, init_state: Callable[[], "TrainState"]) -> "TrainState":
        """The TrainState ``init_state()`` would build, as shapes, dtypes
        and this mesh's shardings — traced, so no device memory is touched.
        What a checkpoint restores into (ops/elastic.py)."""
        shapes = jax.eval_shape(init_state)
        return jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes,
            self.state_sharding(shapes),
        )

    # -- lifecycle ---------------------------------------------------------

    def init_state(self, params, model_state: Any = None) -> TrainState:
        """Build TrainState with every array placed per the mesh layout.
        At scale, draw ``params`` under ``jit`` with
        ``out_shardings=params_sharding()`` so each device generates only
        its own shard; an unsharded draw lands whole on the first device
        before the placement here spreads it."""
        p_sh = self.params_sharding()
        params = jax.tree.map(jax.device_put, params, p_sh)
        opt_state = jax.jit(
            self.tx.init,
            out_shardings=self._opt_sharding_for(params, p_sh),
        )(params)
        if self.has_model_state:
            model_state = jax.tree.map(
                jax.device_put, model_state, self.model_state_sharding()
            )
        return TrainState(
            # on the mesh like the rest: left off it, the step's output (the
            # next step's input) is of another type than this one, and the
            # second step traces, lowers and loads the whole program again
            step=jax.device_put(jnp.zeros((), jnp.int32),
                                NamedSharding(self.mesh, PartitionSpec())),
            params=params,
            opt_state=opt_state,
            model_state=model_state if self.has_model_state else {},
        )

    def _opt_sharding_for(self, params, p_sh):
        """Optimizer state sharding: moments follow params, scalars
        replicate. Matched by key *path* — optimizer moments live at paths
        whose suffix is the param's own path (e.g. chain_state[1].mu.dense1.w
        ends in dense1.w), so each moment inherits exactly its param's
        layout. Shape-based matching would collide for same-shape params
        with different shardings (llama wq vs wo)."""
        from jax.tree_util import tree_flatten_with_path

        shapes = jax.eval_shape(self.tx.init, params)
        p_flat, _ = tree_flatten_with_path(params)
        psh_flat = jax.tree.leaves(
            p_sh, is_leaf=lambda x: isinstance(x, NamedSharding)
        )
        by_path = {}
        for (path, leaf), sh in zip(p_flat, psh_flat):
            by_path[tuple(str(k) for k in path)] = (leaf.shape, sh)
        replicated = NamedSharding(self.mesh, PartitionSpec())

        def pick(path, leaf):
            keys = tuple(str(k) for k in path)
            for start in range(len(keys)):
                hit = by_path.get(keys[start:])
                if hit is not None and hit[0] == leaf.shape:
                    return hit[1]
            return replicated

        o_flat, o_def = tree_flatten_with_path(shapes)
        return jax.tree.unflatten(o_def, [pick(p, l) for p, l in o_flat])

    # -- the step ----------------------------------------------------------

    def _bare_step(self, state: TrainState, batch):
        """The un-jitted step body: what ``train_step`` jits."""
        # the two scopes put the step's phases into every operation's
        # `op_name` (metadata only): a device trace then splits forward,
        # backward, layer replay and optimizer (PERF.md §3)
        metrics = {}
        with jax.named_scope("model"):
            if self.has_model_state:
                (loss, new_ms), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True
                )(state.params, state.model_state, batch)
            else:
                (loss, scalars), grads = jax.value_and_grad(
                    self._loss_and_scalars, has_aux=True
                )(state.params, batch)
                new_ms = state.model_state
                metrics.update(scalars)
        metrics["loss"] = loss
        with jax.named_scope("optimizer"):
            updates, new_opt = self.tx.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
            if self.config.grad_clip_norm > 0:
                # free when clipping: XLA CSEs this with the clip's norm.
                # When not clipping it would be an extra full pass over the
                # gradients, so the metric is only emitted alongside a clip.
                metrics["grad_norm"] = optax.global_norm(grads)
        return (
            TrainState(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                model_state=new_ms,
            ),
            metrics,
        )

    def _loss_and_scalars(self, params, batch):
        """(loss, the loss's named scalars: none where it returns its
        value alone)."""
        out = self._loss_fn(params, batch)
        return out if isinstance(out, tuple) else (out, {})

    def _jit_wrap(self, fn, state, batch_example):
        """jit a (state, batch) -> (state, metrics) function with the
        trainer's shardings + donation."""
        state_sh = self.state_sharding(state)
        # every metric is a replicated scalar, whatever the loss names
        metrics_sh = NamedSharding(self.mesh, PartitionSpec())
        return jax.jit(
            fn,
            in_shardings=(state_sh, self.batch_sharding(batch_example)),
            out_shardings=(state_sh, metrics_sh),
            donate_argnums=(0,) if self._donate else (),
        )

    def train_step(self, state: TrainState, batch):
        if self._step_fn is None:
            self._step_fn = self._jit_wrap(self._bare_step, state, batch)
        return self._step_fn(state, batch)

    def compile(self, state: TrainState, batch):
        """AOT-compile the step (returns the lowered+compiled executable;
        also caches it as the active step fn)."""
        if self._step_fn is None:
            self._step_fn = self._jit_wrap(self._bare_step, state, batch)
        return self._step_fn.lower(state, batch).compile()
