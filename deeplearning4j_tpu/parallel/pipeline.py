"""Pipeline parallelism: GPipe-style microbatching over the 'pipe' axis.

Reference parity: none to mirror — the reference never had pipeline
parallelism (SURVEY.md §2.5 PP row: "stage sharding over pod slices +
microbatch loop" is a new TPU-native capability).

TPU-native design (scaling-book recipe, not a port):
- The model is decomposed into S structurally-identical stages whose
  parameters carry a leading stage axis sharded over the mesh's 'pipe'
  axis — each device (column) holds exactly its stage's weights.
- One `shard_map` over 'pipe' runs the classic GPipe schedule INSIDE a
  single jitted computation: at tick t each stage processes its in-flight
  microbatch and `lax.ppermute` rotates activations to the next stage
  over ICI. M microbatches drain in M+S-1 ticks (the bubble).
- `ppermute` is differentiable, so `jax.grad` through the pipelined
  forward yields the reverse pipeline schedule automatically — no
  hand-written backward pass, unlike every CUDA pipeline runtime.
- Composes with DP/TP: the same step function jits over a
  (pipe, data, model) mesh; batch stays sharded on 'data', stage weights
  may additionally shard on 'model'.

The bubble fraction is (S-1)/(M+S-1); choose microbatches >> stages.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, PIPE_AXIS, DeviceMesh


def stage_sharding(mesh: DeviceMesh, ndim: int) -> NamedSharding:
    """Sharding for stage-stacked parameters: leading axis over 'pipe'."""
    spec = (PIPE_AXIS,) + (None,) * (ndim - 1)
    return NamedSharding(mesh.mesh, PartitionSpec(*spec))


def place_stage_params(mesh: DeviceMesh, stage_params):
    """Device-put a pytree of (S, ...) stage-stacked params so each pipe
    column holds its own stage's slice."""
    return jax.tree_util.tree_map(
        lambda p: jax.device_put(p, stage_sharding(mesh, jnp.ndim(p))),
        stage_params)


def pipeline_forward(stage_fn: Callable, mesh: DeviceMesh,
                     microbatch_spec: Optional[PartitionSpec] = None,
                     extra_specs: Tuple = (),
                     param_specs=None):
    """Build fn(stage_params, microbatches, *extra) -> outputs running the
    GPipe schedule over the mesh's 'pipe' axis.

    stage_fn(params_slice, x, *extra) -> y must keep y.shape == x.shape
    (classic homogeneous-stage pipelining, e.g. transformer blocks).
    microbatches: (M, mb, ...); output: (M, mb, ...) after all S stages.
    extra args are replicated (e.g. an attention mask).

    Composition: on a (pipe, data, ...) mesh the microbatch dim 1 shards
    over 'data' by default, so each pipe column runs data-parallel
    columns of the same stage; stage_fn may additionally use explicit
    'model'-axis collectives for in-stage tensor parallelism —
    ``param_specs`` (a pytree of PartitionSpecs matching stage_params,
    each leading with PIPE_AXIS) declares per-leaf Megatron shardings,
    and stage_fn closes row-parallel contractions with
    ``lax.psum(..., 'model')``.
    """
    S = mesh.axis_size(PIPE_AXIS)

    pspec = PartitionSpec(PIPE_AXIS)
    if microbatch_spec is None:
        microbatch_spec = (PartitionSpec(None, DATA_AXIS)
                           if DATA_AXIS in mesh.axis_names
                           else PartitionSpec())
    xspec = microbatch_spec

    def _pp(stage_params, microbatches, *extra):
        stage = lax.axis_index(PIPE_AXIS)
        M = microbatches.shape[0]
        total = M + S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (clamped; injected garbage past
            # M-1 never reaches the output window), others take the
            # rotated activation
            idx = jnp.clip(t, 0, M - 1)
            inj = lax.dynamic_index_in_dim(microbatches, idx, 0,
                                           keepdims=False)
            x = jnp.where(stage == 0, inj, buf)
            y = stage_fn(jax.tree_util.tree_map(lambda p: p[0],
                                                stage_params), x, *extra)
            # last stage banks its result for microbatch t-(S-1)
            oidx = jnp.clip(t - (S - 1), 0, M - 1)
            bank = jnp.logical_and(stage == S - 1, t >= S - 1)
            outs = lax.cond(
                bank,
                lambda o: lax.dynamic_update_index_in_dim(
                    o, y.astype(o.dtype), oidx, 0),
                lambda o: o, outs)
            buf = lax.ppermute(y, PIPE_AXIS, perm)
            return buf, outs

        buf = jnp.zeros_like(microbatches[0])
        outs = jnp.zeros_like(microbatches)
        buf, outs = lax.fori_loop(0, total, tick, (buf, outs),
                                  unroll=False)
        # results live on the last stage; share them with every column so
        # the loss is computable anywhere (psum of one-hot contribution)
        outs = jnp.where(stage == S - 1, outs, jnp.zeros_like(outs))
        return lax.psum(outs, PIPE_AXIS)

    def fn(stage_params, microbatches, *extra):
        pspecs = (param_specs if param_specs is not None else
                  jax.tree_util.tree_map(lambda _: pspec, stage_params))
        kw = dict(mesh=mesh.mesh,
                  in_specs=(pspecs, xspec) + tuple(
                      extra_specs or (xspec,) * len(extra)),
                  out_specs=xspec)
        return shard_map(_pp, check_vma=False, **kw)(
            stage_params, microbatches, *extra)

    return fn


def _default_sgd(p, g):
    return jax.tree_util.tree_map(lambda a, b: a - 1e-2 * b, p, g)


def split_microbatches(x, n_micro: int):
    """(B, ...) -> (M, B/M, ...) microbatches."""
    B = x.shape[0]
    if B % n_micro:
        raise ValueError(f"batch {B} not divisible into {n_micro} microbatches")
    return x.reshape(n_micro, B // n_micro, *x.shape[1:])


def merge_microbatches(x):
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])


def pipeline_train_step(stage_fn: Callable, loss_fn: Callable,
                        mesh: DeviceMesh, n_micro: int,
                        optimizer_update: Optional[Callable] = None):
    """One jitted GPipe training step.

    stage_fn(params_slice, x) -> y  (homogeneous stages)
    loss_fn(final_activations (B, ...), labels) -> scalar
    optimizer_update(params, grads) -> new params  (default: SGD 1e-2)

    Returns step(stage_params, head_params, x, labels) ->
    (new_stage_params, new_head_params, loss): gradient flows back through
    the pipeline (reverse schedule generated by AD), gradients for stage
    weights land sharded on their own pipe column.
    """
    fwd = pipeline_forward(stage_fn, mesh)
    if optimizer_update is None:
        optimizer_update = _default_sgd

    def loss_of(stage_params, head_params, x, labels):
        mb = split_microbatches(x, n_micro)
        y = merge_microbatches(fwd(stage_params, mb))
        return loss_fn(y, head_params, labels)

    @jax.jit
    def step(stage_params, head_params, x, labels):
        (loss), grads = jax.value_and_grad(loss_of, argnums=(0, 1))(
            stage_params, head_params, x, labels)
        gs, gh = grads
        return (optimizer_update(stage_params, gs),
                optimizer_update(head_params, gh), loss)

    return step


def pipeline_model_train_step(embed_fn: Callable, stage_fn: Callable,
                              head_loss_fn: Callable, mesh: DeviceMesh,
                              n_micro: int,
                              optimizer_update: Optional[Callable] = None,
                              stage_param_specs=None):
    """One jitted train step for the NON-homogeneous model shape
    embed → homogeneous trunk → head (round-4 Weak #8: only same-shape
    trunks could be pipelined).

    TPU-native composition: the trunk — the only part with S
    structurally-identical stages — runs the GPipe schedule over the
    'pipe' axis; ``embed_fn`` (token/position lookup, arbitrary input
    shape → trunk shape) and ``head_loss_fn`` (trunk shape → scalar
    loss, e.g. final LN + tied-vocab logits + CE) run as ordinary SPMD
    computations around it in the SAME jit, sharded over 'data' (and
    'model' where their params carry TP specs). Their FLOPs are tiny
    next to the trunk's, so pinning them to pipe ranks (the GPU
    runtimes' approach) would only add bubble.

    embed_fn(embed_params, *inputs) -> (B, ...) trunk input
    stage_fn(stage_params_slice, h) -> h       (homogeneous trunk)
    head_loss_fn(head_params, h, *labels) -> scalar loss
    Returns step((embed_p, stage_p, head_p), inputs_tuple, labels_tuple)
    -> (new_params_triple, loss).
    """
    fwd = pipeline_forward(stage_fn, mesh, param_specs=stage_param_specs)
    if optimizer_update is None:
        optimizer_update = _default_sgd

    def loss_of(params, inputs, labels):
        embed_p, stage_p, head_p = params
        h = embed_fn(embed_p, *inputs)
        mb = split_microbatches(h, n_micro)
        y = merge_microbatches(fwd(stage_p, mb))
        return head_loss_fn(head_p, y, *labels)

    @jax.jit
    def step(params, inputs, labels):
        loss, grads = jax.value_and_grad(loss_of)(params, inputs, labels)
        new = tuple(optimizer_update(p, g) for p, g in zip(params, grads))
        return new, loss

    return step


def sequential_forward(stage_fn: Callable, stage_params, x, *extra):
    """Reference semantics: run the S stages back-to-back on one device —
    the numerics-equality baseline for the pipelined schedule."""
    S = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    y = x
    for s in range(S):
        p = jax.tree_util.tree_map(lambda a: a[s], stage_params)
        y = stage_fn(p, y, *extra)
    return y
