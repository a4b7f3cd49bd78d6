"""Mixture-of-Experts FFN: token-choice top-k routing, capacity-bounded,
sort-based dispatch (dropless up to the capacity factor).

Two dispatch paths:

  moe_apply          single-device / small-token path: sort + scatter into
                     an [E, C, d] buffer. Correct everywhere, but under
                     GSPMD auto-partitioning the scatter/gather lowers to
                     DENSE [T·k, d] u32 index maps — 60+ GB/device at
                     qwen3-moe's 1M-token training batch.
  moe_apply_sharded  production path: explicit `shard_map`. Tokens stay
                     sharded on the batch axes, dispatch scatters are
                     shard-LOCAL (tiny), expert parallelism is a real
                     `all_to_all` over the model axis, and the FSDP dim of
                     the expert weights is all-gathered in-block. This is
                     the TPU-native mapping of token-choice MoE (DESIGN.md
                     §4); non-divisible expert counts (granite's 40 on a
                     16-way axis) are zero-padded to the axis size with
                     router logits pinned to -inf for dead experts.

_apply_mlp picks the sharded path whenever a policy is installed and the
shapes divide; tests pin both paths against the same dense reference.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import dense_init


def moe_init(key, d_model: int, d_ff: int, n_experts: int, *, gated=True,
             dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], (d_model, n_experts), (0,), jnp.float32),
        "w_up": dense_init(ks[1], (n_experts, d_model, d_ff), (1,), dtype),
        "w_down": dense_init(ks[2], (n_experts, d_ff, d_model), (1,), dtype),
    }
    if gated:
        p["w_gate"] = dense_init(ks[3], (n_experts, d_model, d_ff), (1,), dtype)
    return p


def capacity(tokens: int, top_k: int, n_experts: int, factor: float = 1.25) -> int:
    c = int(math.ceil(tokens * top_k / n_experts * factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for lane alignment


def _expert_ffn(buf, p_up, p_gate, p_down, act: str):
    """buf: [E, C, d] → [E, C, d] through the per-expert gated FFN."""
    up = jnp.einsum("ecd,edf->ecf", buf, p_up)
    if p_gate is not None:
        g = jnp.einsum("ecd,edf->ecf", buf, p_gate)
        h = (jax.nn.gelu(g) if act == "gelu" else jax.nn.silu(g)) * up
    else:
        h = jax.nn.gelu(up) if act == "gelu" else jax.nn.silu(up)
    return jnp.einsum("ecf,efd->ecd", h, p_down)


def _dispatch_combine(xt, logits, top_k: int, C: int, E: int, ffn):
    """Shared local dispatch: sort-by-expert, capacity-bounded scatter,
    expert FFN callback, weighted combine. xt: [T, d] (local)."""
    T, d = xt.shape
    probs = jax.nn.softmax(logits, axis=-1)
    gate_w, gate_e = jax.lax.top_k(probs, top_k)  # [T, k]
    gate_w = gate_w / jnp.maximum(gate_w.sum(-1, keepdims=True), 1e-9)

    # load-balancing auxiliary loss (Switch-style), local statistics
    me = jnp.zeros((E,)).at[gate_e.reshape(-1)].add(1.0) / (T * top_k)
    pe = probs.mean(0)
    aux = E * jnp.sum(me * pe)

    flat_e = gate_e.reshape(T * top_k)
    flat_t = jnp.arange(T * top_k) // top_k
    order = jnp.argsort(flat_e, stable=True)
    se, st = flat_e[order], flat_t[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * top_k, dtype=jnp.int32) - starts[se]
    keep = pos < C
    slot = jnp.where(keep, se * C + pos, E * C)  # overflow slot dropped

    buf = jnp.zeros((E * C + 1, d), xt.dtype).at[slot].set(xt[st])
    out = ffn(buf[: E * C].reshape(E, C, d))  # [E, C, d]

    vals = out.reshape(E * C, d)[jnp.clip(slot, 0, E * C - 1)]
    w = (gate_w.reshape(T * top_k)[order] * keep).astype(xt.dtype)
    y = jnp.zeros((T, d), xt.dtype).at[st].add(vals * w[:, None])
    return y, aux


def moe_apply(p, x, *, top_k: int, act: str = "silu", capacity_factor: float = 1.25):
    """Reference path. x: [B, S, d] -> (y [B, S, d], aux_loss scalar)."""
    B, S, d = x.shape
    T = B * S
    E = p["router"].shape[1]
    C = capacity(T, top_k, E, capacity_factor)
    xt = x.reshape(T, d)
    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"])
    ffn = lambda buf: _expert_ffn(buf, p["w_up"], p.get("w_gate"), p["w_down"], act)
    y, aux = _dispatch_combine(xt, logits, top_k, C, E, ffn)
    return y.reshape(B, S, d), aux


def moe_apply_sharded(p, x, *, top_k: int, act: str = "silu",
                      capacity_factor: float = 1.25, policy=None):
    """Explicit-EP path (see module docstring). Requires: policy set, batch
    divisible by the batch axes, E (padded) divisible by the model axis."""
    B, S, d = x.shape
    E = p["router"].shape[1]
    tp = policy.tp_size
    E_pad = -(-E // tp) * tp  # zero-pad dead experts (granite: 40 -> 48)
    batch_axes = tuple(policy.batch)
    model_ax = policy.model
    # Shard tokens over the model axis too when the sequence divides: this
    # matches the seq-sharded residual layout (zero resharding on entry)
    # and — critically — dispatches each token ONCE. With batch-only
    # sharding every model rank re-dispatches the same tokens: correct,
    # but tp× redundant compute (§Perf iteration 1).
    seq_sharded = S % tp == 0 and S > 1
    n_shards = policy.dp_size * (tp if seq_sharded else 1)
    T_loc = (B * S) // n_shards
    C_loc = capacity(T_loc, top_k, E_pad, capacity_factor)

    gated = "w_gate" in p

    def block(x_l, router, w_up, w_gate, w_down):
        # x_l: [B_loc, S, d]; w_*: [E_loc, d_loc_fsdp, f] local shards
        T = x_l.shape[0] * x_l.shape[1]
        xt = x_l.reshape(T, d)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
        if E_pad > E:
            logits = jnp.pad(logits, ((0, 0), (0, E_pad - E)),
                             constant_values=-jnp.inf)

        # gather the FSDP shard of the expert weights (ZeRO-3 style)
        w_up = jax.lax.all_gather(w_up, batch_axes, axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down, batch_axes, axis=1, tiled=True)
        w_gate_g = (jax.lax.all_gather(w_gate, batch_axes, axis=1, tiled=True)
                    if gated else None)

        # checkpoint: the expert hiddens ([C·tp, ff], the largest activation
        # in MoE training) are recomputed in backward instead of saved
        expert_ffn = jax.checkpoint(
            lambda b: _expert_ffn(b, w_up, w_gate_g, w_down, act))

        def ffn(buf):  # buf: [E_pad, C_loc, d] local
            # all_to_all: experts scatter to their owner rank; tokens from
            # every rank concatenate on the capacity axis
            buf = jax.lax.all_to_all(buf, model_ax, split_axis=0, concat_axis=1,
                                     tiled=True)  # [E_loc, C_loc*tp, d]
            out = expert_ffn(buf)
            return jax.lax.all_to_all(out, model_ax, split_axis=1, concat_axis=0,
                                      tiled=True)  # [E_pad, C_loc, d]

        y, aux = _dispatch_combine(xt, logits, top_k, C_loc, E_pad, ffn)
        aux = jax.lax.pmean(aux, batch_axes + ((model_ax,) if seq_sharded else ()))
        return y.reshape(x_l.shape), aux

    fs = batch_axes
    wspec = P(model_ax, fs, None)
    xspec = (P(batch_axes, model_ax, None) if seq_sharded
             else P(batch_axes, None, None))
    out_y, aux = jax.shard_map(
        block, check_vma=False,
        in_specs=(xspec, P(None, None), wspec, wspec, wspec),
        out_specs=(xspec, P()),
    )(x, p["router"], _pad_e(p["w_up"], E_pad),
      _pad_e(p.get("w_gate"), E_pad) if gated else _zero_like_up(p, E_pad),
      _pad_e(p["w_down"], E_pad))
    return out_y, aux


def _pad_e(w, E_pad):
    if w is None or w.shape[0] == E_pad:
        return w
    return jnp.pad(w, ((0, E_pad - w.shape[0]), (0, 0), (0, 0)))


def _zero_like_up(p, E_pad):
    w = p["w_up"]
    return jnp.zeros((E_pad,) + w.shape[1:], w.dtype)


def sharded_path_ok(policy, x_shape, n_experts: int) -> bool:
    """Static check: can moe_apply_sharded run for these shapes?"""
    if policy is None:
        return False
    B, S, _ = x_shape
    return (B * S) % policy.dp_size == 0 and B % policy.dp_size == 0
