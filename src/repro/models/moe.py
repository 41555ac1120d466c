"""Mixture-of-Experts FFN (DeepSeek-V2 style: shared + routed, top-k).

Expert parallelism is implemented with ``jax.shard_map`` and explicit
``all_to_all`` collectives — the production EP pattern:

  * tokens live on the ``(pod, data)`` axes, experts on ``model``;
  * each shard routes its local tokens, packs them into per-expert capacity
    buffers with a *local* one-hot rank (no global sort, no cross-shard
    scatter), and exchanges buffers along ``model`` with one tiled
    ``all_to_all`` each way;
  * expert weights are stored ``[E, D, F]`` sharded (E over ``model``,
    D/F over ``data``) and FSDP-gathered over ``data`` at use.

Over-capacity tokens are dropped (standard capacity-factor policy); their
combine weight is zero so the residual path carries them unchanged.

When no mesh is active (CPU tests) the same math runs unsharded.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import ParamBuilder


def moe_init(pb: ParamBuilder, cfg: ModelConfig):
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.n_routed_experts
    sub = ParamBuilder(pb.key(), pb.dtype)
    sub.dense("router", d, e, "embed", None, scale=0.02)
    scale = 1.0 / (d ** 0.5)
    for nm, shape, axes in (
            ("w1", (e, d, f), ("experts", "embed", None)),
            ("w3", (e, d, f), ("experts", "embed", None)),
            ("w2", (e, f, d), ("experts", "ff_exp", None))):
        sub.table(nm, shape, axes, scale=scale)
    if cfg.n_shared_experts:
        from .layers import swiglu_init
        swiglu_init(sub, "shared", d, cfg.n_shared_experts * f)
    p, s = sub.build()
    pb.sub("moe", p, s)
    return pb


def route(p, x, cfg: ModelConfig):
    """Router: softmax over routed experts, top-k, renormalized weights."""
    logits = (x.astype(jnp.float32)
              @ p["router"]["w"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.moe_top_k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return top_p, top_i, probs


def _expert_ffn(w1, w3, w2, x):
    """Batched per-expert SwiGLU: ``x [E, C, D]`` -> ``[E, C, D]``."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, w1.astype(x.dtype)))
    u = jnp.einsum("ecd,edf->ecf", x, w3.astype(x.dtype))
    return jnp.einsum("ecf,efd->ecd", h * u, w2.astype(x.dtype))


def _pack(xf, top_i, top_p, e: int, cap: int):
    """Pack tokens into per-expert capacity buffers (local, no collectives).

    ``xf [N, D]``; ``top_i/top_p [N, K]``.  Returns buffer ``[E, cap, D]``,
    plus gather metadata to unpack.  Slot rank = running count of earlier
    (token, k) pairs routed to the same expert.
    """
    n, k = top_i.shape
    flat_e = top_i.reshape(-1)                                   # [N*K]
    oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)              # [N*K, E]
    # rank of each (token, k) pair within its expert = exclusive running count
    rank = jnp.einsum("ne,ne->n", jnp.cumsum(oh, axis=0) - oh, oh)
    keep = rank < cap
    slot = jnp.where(keep, rank, cap)                            # overflow row
    buf = jnp.zeros((e, cap + 1, xf.shape[-1]), xf.dtype)
    src = jnp.repeat(xf, k, axis=0)
    buf = buf.at[flat_e, slot].set(src)
    return buf[:, :cap], flat_e, slot, keep


def _unpack(buf_out, flat_e, slot, keep, top_p, n: int, k: int):
    """Gather expert outputs back to token order and combine with weights."""
    safe_slot = jnp.minimum(slot, buf_out.shape[1] - 1)
    y = buf_out[flat_e, safe_slot]                               # [N*K, D]
    w = (top_p.reshape(-1) * keep).astype(y.dtype)
    return (y * w[:, None]).reshape(n, k, -1).sum(axis=1)


def moe_ffn_local(p, x, cfg: ModelConfig):
    """Single-device reference path (tests, smoke configs)."""
    b, l, d = x.shape
    xf = x.reshape(-1, d)
    top_p, top_i, _ = route(p, xf, cfg)
    n = xf.shape[0]
    cap = max(int(n * cfg.moe_top_k / cfg.n_routed_experts
                  * cfg.moe_capacity_factor), cfg.moe_top_k)
    buf, flat_e, slot, keep = _pack(xf, top_i, top_p,
                                    cfg.n_routed_experts, cap)
    buf_out = _expert_ffn(p["w1"], p["w3"], p["w2"], buf)
    y = _unpack(buf_out, flat_e, slot, keep, top_p, n, cfg.moe_top_k)
    return y.reshape(b, l, d)


def moe_ffn_ep(p, x, cfg: ModelConfig, mesh, dp_axes: tuple, tp_axis: str,
               fsdp_axis: str = "data"):
    """Expert-parallel path: shard_map + all_to_all over ``tp_axis``.

    Tokens are sharded over ``dp_axes`` (pod x data); expert weights are
    stored (E over ``tp_axis``) x (D/F over ``fsdp_axis``) and gathered over
    the *intra-pod* axis only — cross-pod (DCN) links never carry weights.
    """
    e = cfg.n_routed_experts
    tp = mesh.shape[tp_axis]
    e_loc = e // tp
    assert e_loc * tp == e, (e, tp)
    gather_w = fsdp_axis in mesh.shape and mesh.shape[fsdp_axis] > 1
    dp_total = 1
    for a in dp_axes:
        dp_total *= mesh.shape[a]

    # static cost model: move weights (gather) vs. move activations (psum).
    n_tokens_loc = (x.shape[0] // dp_total) * x.shape[1]
    cap_est = max(int(-(-n_tokens_loc // tp) * cfg.moe_top_k / e
                      * cfg.moe_capacity_factor), cfg.moe_top_k)
    act_bytes = 3 * e_loc * tp * cap_est * max(cfg.moe_d_ff, cfg.d_model)
    wgt_bytes = 3 * e_loc * cfg.d_model * cfg.moe_d_ff
    stationary = gather_w and act_bytes < wgt_bytes

    def inner(xl, router, w1, w3, w2):
        bl, l, d = xl.shape
        xf = xl.reshape(-1, d)
        n_loc = xf.shape[0]
        # tokens are replicated over tp_axis at entry: each tp rank takes its
        # contiguous 1/TP slice so every token rides the wire exactly once.
        n_pad = -(-n_loc // tp) * tp
        if n_pad != n_loc:
            xf = jnp.pad(xf, ((0, n_pad - n_loc), (0, 0)))
        n_m = n_pad // tp
        rank = jax.lax.axis_index(tp_axis)
        xm = jax.lax.dynamic_slice_in_dim(xf, rank * n_m, n_m)
        top_p, top_i, _ = route({"router": {"w": router}}, xm, cfg)
        cap = max(int(n_m * cfg.moe_top_k / e * cfg.moe_capacity_factor),
                  cfg.moe_top_k)
        buf, flat_e, slot, keep = _pack(xm, top_i, top_p, e, cap)
        # exchange: my buffers for peer experts <-> peer buffers for mine
        buf = jax.lax.all_to_all(buf.reshape(tp, e_loc, cap, d), tp_axis,
                                 split_axis=0, concat_axis=0, tiled=False)
        #   [TP, E_loc, cap, D] with axis 0 = source peer
        buf = buf.transpose(1, 0, 2, 3).reshape(e_loc, tp * cap, d)
        if gather_w and not stationary:
            # FSDP-gather my experts' weights (intra-pod links)
            w1 = jax.lax.all_gather(w1, fsdp_axis, axis=1, tiled=True)
            w3 = jax.lax.all_gather(w3, fsdp_axis, axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, fsdp_axis, axis=1, tiled=True)
        if gather_w and stationary:
            # §Perf C1 (decode): weights stay sharded; slice the activation
            # D/F dims locally and psum partial products over the fsdp axis —
            # wire bytes scale with the (tiny) token buffer, not the weights.
            r = jax.lax.axis_index(fsdp_axis)
            d_loc, f_loc = w1.shape[1], w2.shape[1]
            xd = jax.lax.dynamic_slice_in_dim(buf, r * d_loc, d_loc, axis=-1)
            h = jax.lax.psum(
                jnp.einsum("ecd,edf->ecf", xd, w1.astype(xd.dtype)),
                fsdp_axis)
            u = jax.lax.psum(
                jnp.einsum("ecd,edf->ecf", xd, w3.astype(xd.dtype)),
                fsdp_axis)
            hu = jax.nn.silu(h) * u
            hf = jax.lax.dynamic_slice_in_dim(hu, r * f_loc, f_loc, axis=-1)
            out = jax.lax.psum(
                jnp.einsum("ecf,efd->ecd", hf, w2.astype(hf.dtype)),
                fsdp_axis)
        else:
            out = _expert_ffn(w1, w3, w2, buf)
        out = out.reshape(e_loc, tp, cap, d).transpose(1, 0, 2, 3)
        out = jax.lax.all_to_all(out, tp_axis, split_axis=0, concat_axis=0,
                                 tiled=False).reshape(e, cap, d)
        ym = _unpack(out, flat_e, slot, keep, top_p, n_m, cfg.moe_top_k)
        # re-replicate over tp_axis (token slices back together)
        y = jax.lax.all_gather(ym, tp_axis, axis=0, tiled=True)[:n_loc]
        return y.reshape(bl, l, d)

    spec_x = P(dp_axes if len(dp_axes) > 1 else dp_axes[0], None, None)
    w_spec = P(tp_axis, fsdp_axis if gather_w else None, None)
    return jax.shard_map(
        inner, mesh=mesh,
        in_specs=(spec_x, P(None, None), w_spec, w_spec, w_spec),
        out_specs=spec_x,
        check_vma=False,
    )(x, p["router"]["w"], p["w1"], p["w3"], p["w2"])


def moe_ffn(p, x, cfg: ModelConfig, mesh=None, dp_axes=("data",),
            tp_axis="model"):
    """Dispatch to EP or local path; always adds the shared experts."""
    if mesh is not None and mesh.shape.get(tp_axis, 1) > 1 \
            and cfg.n_routed_experts % mesh.shape[tp_axis] == 0:
        y = moe_ffn_ep(p, x, cfg, mesh, dp_axes, tp_axis)
    else:
        y = moe_ffn_local(p, x, cfg)
    if cfg.n_shared_experts:
        from .layers import swiglu
        y = y + swiglu(x, p["shared"])
    return y
