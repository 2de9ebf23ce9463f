"""SPMD train-step builders: mesh in, jitted sharded step out.

Two composition modes, matching how TPU programs are actually written:

- `make_train_step`: gspmd mode — params/batch carry NamedShardings
  (dp/fsdp/tp/ep) and XLA inserts all collectives (scaling-book recipe).
- `make_sp_pp_train_step`: manual mode — the model runs inside shard_map for
  the axes XLA cannot infer (ring attention over sp, GPipe over pp).

(reference equivalent: Ray Train wires torch DDP/NCCL per worker,
train/torch/config.py:122; here parallelism is in-program.)
"""

from __future__ import annotations

from typing import Callable

import jax
import optax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu._private.constants import MESH_AXIS_DP, MESH_AXIS_FSDP
from ray_tpu.parallel import DEFAULT_RULES, param_shardings


def make_train_step(
    loss_fn: Callable,          # loss_fn(params, batch) -> scalar
    logical_axes,               # pytree of logical tuples matching params
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    *,
    batch_spec: P = P((MESH_AXIS_DP, MESH_AXIS_FSDP)),
    donate: bool = True,
    partition_rules=None,       # [(regex, PartitionSpec)] over param paths
    params_template=None,       # params (or their eval_shape) for the rules
    zero_axis: str | None = None,  # ZeRO-1: shard opt state over this axis
):
    """Returns (step, shard_params, batch_sharding).

    step(params, opt_state, batch) -> (params, opt_state, loss); all
    collectives (grad psum over dp, fsdp all-gathers/reduce-scatters, tp
    activation collectives) are inserted by XLA from the shardings.

    Two ways to name the param shardings: `logical_axes` (pytree of
    logical-dimension tuples, mesh.py rules) or `partition_rules` + a
    `params_template` (regex over '/'-joined param paths — zero.py's
    `match_partition_rules`). With `zero_axis` (requires the rules form)
    the jitted step additionally pins the optimizer state to ZeRO-1
    shardings over that axis, so XLA lowers reduce-scatter -> 1/W update
    -> all-gather natively (see train/zero.py; init the state with
    `zero.make_zero_train_step`'s init_opt_state to never materialize it
    unsharded)."""
    if partition_rules is not None:
        if params_template is None:
            raise ValueError("partition_rules needs params_template "
                             "(a params pytree or its eval_shape)")
        from ray_tpu.train import zero as zero_mod

        p_shardings = zero_mod.param_shardings_from_rules(
            partition_rules, params_template, mesh)
    else:
        if zero_axis is not None:
            raise ValueError(
                "zero_axis needs partition_rules + params_template: the "
                "optimizer-state shardings are derived from the rules")
        p_shardings = param_shardings(mesh, logical_axes, DEFAULT_RULES)
    batch_sharding = NamedSharding(mesh, batch_spec)

    def step(params, opt_state, batch):
        # traced with the mesh ambient: an op GSPMD cannot partition (the
        # Pallas flash kernel, ops/attention.py) reads it to shard_map itself
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    jit_kwargs: dict = {"donate_argnums": (0, 1) if donate else ()}
    if zero_axis is not None:
        opt_shardings = zero_mod.zero_opt_shardings(
            optimizer, params_template, partition_rules, mesh,
            axis=zero_axis)
        jit_kwargs["out_shardings"] = (p_shardings, opt_shardings,
                                       NamedSharding(mesh, P()))
    jit_step = jax.jit(step, **jit_kwargs)

    def shard_params(params):
        return jax.device_put(params, p_shardings)

    return jit_step, shard_params, batch_sharding


def init_sharded(init_fn: Callable, logical_axes, mesh: Mesh, *args,
                 partition_rules=None):
    """Initialize params directly with their target shardings (no host→device
    reshard of the full tree; XLA initializes each shard in place).
    `partition_rules` ([(regex, PartitionSpec)], zero.py idiom) replaces
    `logical_axes` when given — shapes come from eval_shape of init_fn."""
    if partition_rules is not None:
        from ray_tpu.train import zero as zero_mod

        template = jax.eval_shape(init_fn, *args)
        shardings = zero_mod.param_shardings_from_rules(
            partition_rules, template, mesh)
    else:
        shardings = param_shardings(mesh, logical_axes, DEFAULT_RULES)
    return jax.jit(init_fn, out_shardings=shardings)(*args)


def init_opt_state(optimizer: optax.GradientTransformation, params):
    """`optimizer.init(params)` as one program, every moment placed where
    its parameter lives and the rest (step counts) replicated. A bare
    `jax.jit(optimizer.init)` leaves the whole state on device 0 — zeros
    carry no sharding of their own — and the sharded step then keeps it
    there."""
    replicated = NamedSharding(jax.tree.leaves(params)[0].sharding.mesh, P())
    shardings = optax.tree_map_params(
        optimizer, lambda _, sharding: sharding,
        jax.eval_shape(optimizer.init, params),
        jax.tree.map(lambda x: x.sharding, params),
        transform_non_params=lambda _: replicated)
    return jax.jit(optimizer.init, out_shardings=shardings)(params)


def _spec_axes(spec: P) -> set[str]:
    out: set[str] = set()
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            out.update(entry)
        else:
            out.add(entry)
    return out


def make_sp_pp_train_step(
    shard_loss_fn: Callable,    # (params, batch) -> scalar, called INSIDE shard_map
    param_specs,                # pytree of PartitionSpec for params
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    *,
    batch_spec: P,
    loss_axes: tuple[str, ...],  # mesh axes the per-shard loss is averaged over
):
    """Manual-mode step. The per-shard loss is pmean'd over `loss_axes`; each
    param's gradient is then psum'd over the loss axes it is REPLICATED on
    (axes absent from its spec) — the transpose-correct reduction: psum of the
    1/|axes| cotangent shares reconstitutes the true gradient. Axes present in
    a param's spec (e.g. 'pp' for stage-stacked layers) keep per-shard grads."""
    def _vma(x):
        return jax.typeof(x).vma

    def shard_grad_fn(params, batch):
        def total(p, b):
            l = shard_loss_fn(p, b)
            axes = tuple(ax for ax in loss_axes if ax in _vma(l))
            return jax.lax.pmean(l, axes) if axes else l

        loss, grads = jax.value_and_grad(total)(params, batch)

        def reduce(g, spec):
            axes = tuple(ax for ax in loss_axes
                         if ax not in _spec_axes(spec) and ax in _vma(g))
            return jax.lax.psum(g, axes) if axes else g

        grads = jax.tree.map(reduce, grads, param_specs)
        return loss, grads

    smapped = shard_map(
        shard_grad_fn, mesh=mesh,
        in_specs=(param_specs, batch_spec),
        out_specs=(P(), param_specs),
    )

    def step(params, opt_state, batch):
        loss, grads = smapped(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))
