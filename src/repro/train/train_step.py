"""Training step factory: grads + AdamW under pjit, with optional
microbatch accumulation and compressed cross-pod gradient reduction.

Distribution model (DESIGN.md §9):

* intra-pod: pjit auto-sharding — batch over ``data``, params FSDP over
  ``data`` + TP over ``model`` (XLA inserts the all-gathers/reduce-scatters);
* cross-pod: either (a) the same pjit program with batch over
  ``(pod, data)`` — XLA emits one fused all-reduce over both axes — or
  (b) ``compress_pods=True``: the step is shard_mapped over ``pod`` only
  (``data``/``model`` stay auto), gradients are bf16-compressed before the
  explicit cross-pod ``psum`` — halving the slowest (DCN) wire bytes.
  Compression error feedback is carried in the optimizer state.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .optimizer import AdamWConfig, adamw, apply_updates


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jnp.ndarray


def init_state(params, opt_cfg: AdamWConfig) -> TrainState:
    opt_init, _ = adamw(opt_cfg)
    return TrainState(params=params, opt_state=opt_init(params),
                      step=jnp.zeros((), jnp.int32))


def make_train_step(model, par, opt_cfg: AdamWConfig,
                    microbatches: int = 1, compress_pods: bool = False):
    """Returns ``step(state, batch) -> (state, metrics)`` (to be jitted by
    the caller with in/out shardings)."""
    _, opt_update = adamw(opt_cfg)

    def loss_fn(params, batch):
        return model.loss(params, batch, par)

    def grads_of(params, batch):
        if microbatches == 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        def micro(carry, mb):
            loss_acc, grad_acc = carry
            l, g = jax.value_and_grad(loss_fn)(params, mb)
            return (loss_acc + l,
                    jax.tree.map(jnp.add, grad_acc, g)), None

        def split(x):
            return x.reshape((microbatches, x.shape[0] // microbatches)
                             + x.shape[1:])

        mbs = jax.tree.map(split, batch)
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                            params)
        (loss, grads), _ = jax.lax.scan(micro, (jnp.float32(0), zero), mbs)
        inv = 1.0 / microbatches
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    def apply(state: TrainState, loss, grads):
        updates, opt_state, om = opt_update(grads, state.opt_state,
                                            state.params)
        params = apply_updates(state.params, updates)
        metrics = {"loss": loss, **om}
        return TrainState(params, opt_state, state.step + 1), metrics

    if not compress_pods:
        def step(state: TrainState, batch):
            loss, grads = grads_of(state.params, batch)
            return apply(state, loss, grads)
        return step

    # ---- compressed cross-pod DP: manual over 'pod', auto elsewhere ----
    mesh = par.mesh
    assert mesh is not None and "pod" in mesh.shape, \
        "compress_pods requires a multi-pod mesh"
    npods = mesh.shape["pod"]

    def pod_step(state: TrainState, batch):
        def inner(st, b):
            loss, grads = grads_of(st.params, b)
            # bf16 compression before the cross-pod (DCN) all-reduce:
            # halves wire bytes on the slowest link in the system.
            cgrads = jax.tree.map(lambda g: g.astype(jnp.bfloat16), grads)
            cgrads = jax.lax.psum(cgrads, "pod")
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32) / npods, cgrads)
            loss = jax.lax.psum(loss, "pod") / npods
            return apply(st, loss, grads)

        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(), P("pod")),   # state replicated over pod; batch split
            out_specs=(P(), P()),
            check_vma=False,
            axis_names=frozenset({"pod"}),  # 'data'/'model' stay auto-sharded
        )(state, batch)

    return pod_step
