"""Mixture-of-Experts FFN: top-k routing with capacity-bounded gather
dispatch inside ``shard_map`` (DESIGN.md §5).

Why shard_map here: the classic GShard one-hot dispatch tensor (T, E, C) is
quadratically wasteful at pod scale, and sort-based ragged dispatch has
data-dependent shapes. We instead run the dispatch *per data shard*: tokens
stay local, each local shard gathers its tokens into an (E, C_loc, d) buffer
(C_loc = capacity per shard), runs all experts as a leading batched matmul
with d_ff tensor-sharded over "model", and scatters back. Router compute is
replicated over "model"; overflow tokens fall through on the residual path
(standard capacity-drop semantics, capacity_factor configurable).

Expert weights: (E, d, f) with f sharded over "model" — a uniform rule valid
for both 8-expert (Mixtral) and 128-expert (Llama4) configs. An all-to-all
expert-parallel layout is a recorded §Perf alternative.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from ..configs.base import ArchConfig, MoEConfig
from .params import ParamDecl
from .common import dense_decl, F32


def moe_decl(cfg: ArchConfig) -> dict:
    m = cfg.moe
    E, d, f = m.n_experts, cfg.d_model, m.d_ff
    p = {
        "router": dense_decl(d, E, axes=("fsdp", None)),
        "gate": {"w": ParamDecl((E, d, f), ("expert", "fsdp", "model"), init="fan_in")},
        "up": {"w": ParamDecl((E, d, f), ("expert", "fsdp", "model"), init="fan_in")},
        "down": {"w": ParamDecl((E, f, d), ("expert", "model", "fsdp"), init="fan_in")},
    }
    if m.shared_expert_d_ff:
        from .ffn import ffn_decl
        p["shared"] = ffn_decl(d, m.shared_expert_d_ff, "swiglu")
    return p


def _local_moe(m: MoEConfig, quant: str, tp_axis, dp_axes, x, wr, wg, wu, wd):
    """Per-shard MoE. x: (T_loc, d) local tokens; weights d_ff-sharded.

    When run under shard_map, ``tp_axis`` names the tensor axis (the expert
    d_ff is sharded over it → the down-projection yields partial sums that
    must be psummed) and ``dp_axes`` the token axes (aux loss is pmeaned)."""
    T, d = x.shape
    E, k = m.n_experts, m.top_k
    cap = max(1, int(m.capacity_factor * k * T / E))

    logits = jnp.einsum("td,de->te", x.astype(F32), wr.astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, expert_idx = jax.lax.top_k(probs, k)              # (T, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    flat_expert = expert_idx.reshape(-1)                       # (T·k,)
    # position of each (token, slot) within its expert queue
    onehot = jax.nn.one_hot(flat_expert, E, dtype=jnp.int32)   # (T·k, E)
    pos_in_e = jnp.cumsum(onehot, axis=0) - 1                  # (T·k, E)
    my_pos = jnp.take_along_axis(pos_in_e, flat_expert[:, None], 1)[:, 0]
    keep = my_pos < cap
    slot = jnp.where(keep, flat_expert * cap + my_pos, E * cap)  # overflow → cap bucket

    # gather tokens into (E·cap, d); dropped slots read zeros
    buf = jnp.zeros((E * cap + 1, d), x.dtype)
    tok_idx = jnp.repeat(jnp.arange(T), k)
    buf = buf.at[slot].set(x[tok_idx])
    grouped = buf[:-1].reshape(E, cap, d)

    # batched expert FFN (leading E dim; f sharded over "model" outside)
    def q(w):
        from ..core.quantize import pow2_quantize, pow2_dequantize

        if w.dtype == jnp.uint8:             # packed serving storage
            return pow2_dequantize(w, x.dtype)
        if quant == "pow2":
            wq = pow2_dequantize(pow2_quantize(w), w.dtype)
            return w + jax.lax.stop_gradient(wq - w)
        return w

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", grouped, q(wg))) \
        * jnp.einsum("ecd,edf->ecf", grouped, q(wu))
    y = jnp.einsum("ecf,efd->ecd", h, q(wd))                   # (E, cap, d)

    # scatter back, weighted by the gate
    y_flat = y.reshape(E * cap, d)
    contrib = jnp.where(keep[:, None], y_flat[jnp.minimum(slot, E * cap - 1)], 0.0)
    contrib = contrib * gate_w.reshape(-1)[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(contrib, tok_idx, num_segments=T)
    # aux: load-balance loss (Switch): E · Σ_e f_e · p_e
    me = jnp.mean(onehot.astype(F32).reshape(T, k, E).sum(1), axis=0)
    pe = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(me * pe)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)      # d_ff shards hold partial sums
        if dp_axes:
            aux = jax.lax.pmean(aux, dp_axes)  # replicate the scalar
    return out.astype(x.dtype), aux


def _ep_local_moe(m: MoEConfig, quant: str, n_data: int, tp_axis, dp_last,
                  dp_axes, x, wr, wg, wu, wd):
    """Expert-parallel MoE shard: experts stay resident (sharded over the
    "data" axis), TOKENS move via all_to_all (§Perf iteration for the
    collective-bound MoE decode cells).

    x: (T_loc, d); wg/wu: (E_loc, d, f_loc); wd: (E_loc, f_loc, d).
    Collective traffic per step = 2 × bucket bytes (tokens out and back)
    instead of an all-gather of every expert weight.
    """
    T, d = x.shape
    E, k = m.n_experts, m.top_k
    E_loc = E // n_data

    logits = jnp.einsum("td,de->te", x.astype(F32), wr.astype(F32))
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, expert_idx = jax.lax.top_k(probs, k)
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    flat_e = expert_idx.reshape(-1)                     # (T·k,)
    tok_idx = jnp.repeat(jnp.arange(T), k)
    dst = flat_e // E_loc                               # destination shard
    cap = max(1, int(m.capacity_factor * k * T / n_data))

    # bucket position within (src → dst) lane
    onehot = jax.nn.one_hot(dst, n_data, dtype=jnp.int32)
    pos = jnp.take_along_axis(jnp.cumsum(onehot, 0) - 1, dst[:, None], 1)[:, 0]
    keep = pos < cap
    slot = jnp.where(keep, dst * cap + pos, n_data * cap)

    send_x = jnp.zeros((n_data * cap + 1, d), x.dtype).at[slot].set(x[tok_idx])
    send_e = jnp.full((n_data * cap + 1,), E_loc, jnp.int32).at[slot].set(
        flat_e % E_loc)
    a2a = lambda t: jax.lax.all_to_all(
        t.reshape((n_data, cap) + t.shape[1:]), dp_last, 0, 0, tiled=True)
    recv_x = a2a(send_x[:-1])                           # (n_data, cap, d)
    recv_e = a2a(send_e[:-1])                           # (n_data, cap)

    # group received tokens by local expert
    R = n_data * cap
    rx = recv_x.reshape(R, d)
    re = recv_e.reshape(R)
    cap_e = max(1, int(2 * R / E_loc))
    oh_e = jax.nn.one_hot(re, E_loc + 1, dtype=jnp.int32)
    pos_e = jnp.take_along_axis(jnp.cumsum(oh_e, 0) - 1, re[:, None], 1)[:, 0]
    keep_e = (pos_e < cap_e) & (re < E_loc)
    slot_e = jnp.where(keep_e, re * cap_e + pos_e, E_loc * cap_e)
    buf = jnp.zeros((E_loc * cap_e + 1, d), x.dtype).at[slot_e].set(rx)
    grouped = buf[:-1].reshape(E_loc, cap_e, d)

    def q(w):
        from ..core.quantize import pow2_quantize, pow2_dequantize

        if w.dtype == jnp.uint8:
            return pow2_dequantize(w, x.dtype)
        if quant == "pow2":
            wq = pow2_dequantize(pow2_quantize(w), w.dtype)
            return w + jax.lax.stop_gradient(wq - w)
        return w

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", grouped, q(wg))) \
        * jnp.einsum("ecd,edf->ecf", grouped, q(wu))
    y = jnp.einsum("ecf,efd->ecd", h, q(wd))
    if tp_axis is not None:
        y = jax.lax.psum(y, tp_axis)                    # f_loc partial sums

    # back to received order → all_to_all home → weighted unbucket
    y_flat = y.reshape(E_loc * cap_e, d)
    y_recv = jnp.where(keep_e[:, None],
                       y_flat[jnp.minimum(slot_e, E_loc * cap_e - 1)], 0.0)
    y_home = a2a(y_recv.reshape(R, d)).reshape(R, d)
    contrib = jnp.where(keep[:, None],
                        y_home[jnp.minimum(slot, R - 1)], 0.0)
    contrib = contrib * gate_w.reshape(-1)[:, None].astype(x.dtype)
    out = jax.ops.segment_sum(contrib, tok_idx, num_segments=T)

    me = jnp.mean(jax.nn.one_hot(flat_e, E, dtype=F32).reshape(T, k, E).sum(1), 0)
    aux = E * jnp.sum(me * jnp.mean(probs, axis=0))
    if dp_axes:
        aux = jax.lax.pmean(aux, dp_axes)
    return out.astype(x.dtype), aux


def moe_ffn(cfg: ArchConfig, p: dict, x: jnp.ndarray, mesh=None,
            dp_axes: tuple[str, ...] = ("data",)) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) → (y, aux_loss). Runs per-data-shard under shard_map when
    a mesh is provided; plain local computation otherwise (CPU tests).

    With the serving profile (cfg.serve_tp_only) and n_experts divisible by
    the data axis, dispatch switches to expert-parallel all_to_all
    (_ep_local_moe): expert weights never cross the network."""
    m = cfg.moe
    B, S, d = x.shape
    xf = x.reshape(B * S, d)

    if mesh is not None:
        # tokens shard over the dp axes when they divide; tiny decode
        # batches (e.g. long_500k, batch=1) replicate instead.
        n_dp = 1
        for a in dp_axes:
            n_dp *= mesh.shape[a]
        if (B * S) % n_dp:
            dp_axes = ()
        n_data = mesh.shape["data"]
        use_ep = (cfg.serve_tp_only and m.n_experts % n_data == 0
                  and "data" in (dp_axes or ()))
        if use_ep:
            local = partial(_ep_local_moe, m, cfg.quant, n_data, "model",
                            "data", dp_axes)
            wspec_g = P("data", None, "model")
            wspec_d = P("data", "model", None)
        else:
            local = partial(_local_moe, m, cfg.quant, "model", dp_axes)
            wspec_g = P(None, None, "model")
            wspec_d = P(None, "model", None)
        tok_spec = P(dp_axes if dp_axes else None, None)
        y, aux = shard_map(
            local, mesh=mesh,
            in_specs=(tok_spec, P(None, None), wspec_g, wspec_g, wspec_d),
            out_specs=(tok_spec, P()),
            check_vma=False,
        )(xf, p["router"]["w"], p["gate"]["w"], p["up"]["w"], p["down"]["w"])
    else:
        y, aux = _local_moe(m, cfg.quant, None, None, xf, p["router"]["w"],
                            p["gate"]["w"], p["up"]["w"], p["down"]["w"])
    y = y.reshape(B, S, d)
    if m.shared_expert_d_ff:
        from .ffn import ffn
        y = y + ffn(p["shared"], x, "swiglu", cfg.quant)
    return y, aux