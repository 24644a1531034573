"""Mixture-of-Experts layer with sort-based grouped dispatch.

The classic Mesh-TF one-hot dispatch materializes an (N, E, C) tensor with
C ≈ k·N/E — O(N²k) memory, unusable at 32k–500k tokens. Instead we sort the
(token, expert) assignment pairs by expert id and scatter each expert's
tokens into a fixed-capacity (E, C, D) buffer:

  1. router top-k → ids (N, k), weights (N, k);
  2. stable argsort of flattened ids groups tokens by expert;
  3. slot-in-expert = rank − segment_start (via searchsorted);
  4. scatter tokens into (E, C+1, D); slot ≥ C overflows into a discard
     column (token dropped — capacity_factor controls drop rate);
  5. per-expert SwiGLU via einsum over the (E, C, D) buffer (MXU-friendly);
  6. gather + weighted combine back to (N, D).

Memory is O(k·N·cf·D) — linear in tokens. Router uses f32 softmax; aux
load-balancing loss (Switch-style) is returned for training.

Sharding: the expert axis E of the buffers/weights takes the config's
``expert_axis`` mesh axis ("data" for kimi's 384-expert EP, None for
mixtral's 8 tensor-parallel experts); d_ff takes "model".
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models.layers import fan_in_init, normal_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int  # per-expert hidden
    num_experts: int
    top_k: int
    capacity_factor: float = 1.25
    router_softcap: float | None = None
    ep_axis: str | None = None  # mesh axis for expert parallelism


# Deployment context for the shard_map expert-parallel path (set by the
# launcher; None on CPU/smoke where the dense sort-dispatch path runs).
_EP_MESH = None


def set_ep_mesh(mesh):
    global _EP_MESH
    _EP_MESH = mesh


def init(key, cfg: MoEConfig, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": normal_init(ks[0], (d, e), 0.02, jnp.float32),
        "w_gate": fan_in_init(ks[1], (e, d, f), dtype),
        "w_up": fan_in_init(ks[2], (e, d, f), dtype),
        "w_down": jax.vmap(lambda k: fan_in_init(k, (f, d), dtype))(
            jax.random.split(ks[3], e)
        ),
    }


def capacity(num_tokens: int, cfg: MoEConfig) -> int:
    c = int(cfg.top_k * num_tokens * cfg.capacity_factor / cfg.num_experts)
    return max(c - c % -8, 8)  # round up to 8


def apply(p, x, cfg: MoEConfig):
    """x: (B, S, D) -> (y (B, S, D), aux_loss scalar)."""
    b, s, d = x.shape
    n = b * s
    e, k = cfg.num_experts, cfg.top_k
    c = capacity(n, cfg)
    xt = x.reshape(n, d)

    logits = (xt.astype(jnp.float32) @ p["router"])  # (N, E)
    if cfg.router_softcap:
        logits = cfg.router_softcap * jnp.tanh(logits / cfg.router_softcap)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, k)  # (N, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # Switch-style load-balance auxiliary loss.
    me = jnp.mean(probs, axis=0)  # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(top_ids[:, 0], e, dtype=jnp.float32), axis=0
    )
    aux = e * jnp.sum(me * ce)

    flat_ids = top_ids.reshape(-1)  # (N·k,)
    flat_w = top_w.reshape(-1)
    tok_idx = jnp.repeat(jnp.arange(n), k)
    order = jnp.argsort(flat_ids, stable=True)
    s_ids = flat_ids[order]
    s_tok = tok_idx[order]
    seg_start = jnp.searchsorted(s_ids, jnp.arange(e), side="left")
    slot = jnp.arange(n * k) - seg_start[s_ids]
    slot_c = jnp.where(slot < c, slot, c)  # overflow -> discard column

    # dispatch: (E, C+1, D); discard column c collects dropped tokens.
    buf = jnp.zeros((e, c + 1, d), x.dtype)
    buf = buf.at[s_ids, slot_c].set(xt[s_tok], mode="drop")
    hidden = buf[:, :c, :]

    act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", hidden, p["w_gate"]))
    act = act * jnp.einsum("ecd,edf->ecf", hidden, p["w_up"])
    out = jnp.einsum("ecf,efd->ecd", act, p["w_down"])  # (E, C, D)

    # combine: gather each assignment's expert output, weight, scatter-add.
    out_pad = jnp.concatenate([out, jnp.zeros((e, 1, d), out.dtype)], axis=1)
    gathered = out_pad[s_ids, slot_c]  # (N·k, D); dropped rows are zero
    weighted = gathered * flat_w[order][:, None].astype(gathered.dtype)
    y = jnp.zeros((n, d), x.dtype).at[s_tok].add(weighted)
    return y.reshape(b, s, d), aux


def _round8(c: int) -> int:
    return max(c - c % -8, 8)


def apply_expert_parallel(p, x, cfg: MoEConfig, *, cf2: float = 1.5):
    """shard_map expert-parallel MoE (§Perf, kimi hillclimb).

    GSPMD auto-partitioning of the sort-dispatch scatter/gather across a
    data-sharded expert buffer lowers to full-result all-reduces (measured
    162 TB/chip/step on kimi train_4k). This path makes the communication
    explicit and minimal:

      1. per data-rank: route local tokens, bucket by owner rank
         (capacity C = k·n·cf/R), `all_to_all` over the expert axis;
      2. per owner: group received rows by local expert (capacity
         C2 = R·C·cf2/E_loc), run the TP experts (d_ff sharded on
         "model"), `psum("model")` the F-shard partial outputs in bf16;
      3. `all_to_all` rows back, weighted scatter-add at the source.

    Per-layer per-chip volume ≈ 2·(kN/R)·cf·D·bytes (a2a) +
    2·(kN/R)·cf·D·2B (psum) — O(dispatched tokens), not O(buffer).
    Requires ``set_ep_mesh(mesh)`` and cfg.ep_axis (kimi: "data").
    """
    mesh = _EP_MESH
    assert mesh is not None and cfg.ep_axis is not None
    from jax.sharding import PartitionSpec as P

    data_axis = cfg.ep_axis
    model_axis = "model"
    R = mesh.shape[data_axis]
    M = mesh.shape[model_axis]
    e, k, d = cfg.num_experts, cfg.top_k, cfg.d_model
    e_loc = e // R
    b, s, _ = x.shape
    n = (b // R) * s  # local tokens per data rank (per pod)
    pod = ("pod",) if "pod" in mesh.axis_names else ()
    dp = (pod + (data_axis,)) if pod else data_axis
    if pod:
        n = n // mesh.shape["pod"]
    cap = _round8(int(k * n * cfg.capacity_factor / R))
    cap2 = _round8(min(int(R * cap * cf2 / e_loc), R * cap))

    def local_fn(router, wg, wu, wd, xs):
        b_loc, s_, d_ = xs.shape
        nn = b_loc * s_
        xt = xs.reshape(nn, d_)
        logits = xt.astype(jnp.float32) @ router  # (n, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_ids = jax.lax.top_k(probs, k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

        me = jax.lax.pmean(jnp.mean(probs, axis=0), data_axis)
        ce = jax.lax.pmean(
            jnp.mean(jax.nn.one_hot(top_ids[:, 0], e, dtype=jnp.float32), 0),
            data_axis,
        )
        aux = e * jnp.sum(me * ce)

        # ---- bucket assignments by destination data-rank
        dst = (top_ids // e_loc).reshape(-1)  # (n·k,)
        eloc = (top_ids % e_loc).reshape(-1)
        w_flat = top_w.reshape(-1)
        order = jnp.argsort(dst, stable=True)
        sd = dst[order]
        st = order // k  # source token of each sorted assignment
        seg = jnp.searchsorted(sd, jnp.arange(R), side="left")
        slot = jnp.arange(nn * k) - seg[sd]
        slot_c = jnp.where(slot < cap, slot, cap)  # cap column = discard

        send_x = jnp.zeros((R, cap + 1, d_), xs.dtype)
        send_x = send_x.at[sd, slot_c].set(xt[st], mode="drop")
        send_e = jnp.full((R, cap + 1), -1, jnp.int32)
        send_e = send_e.at[sd, slot_c].set(eloc[order], mode="drop")

        recv_x = jax.lax.all_to_all(send_x[:, :cap], data_axis, 0, 0)
        recv_e = jax.lax.all_to_all(send_e[:, :cap], data_axis, 0, 0)
        rx = recv_x.reshape(R * cap, d_)
        re_ = recv_e.reshape(R * cap)

        # ---- group received rows by local expert
        key2 = jnp.where(re_ >= 0, re_, e_loc)  # empties sort to the end
        order2 = jnp.argsort(key2, stable=True)
        se = key2[order2]
        seg2 = jnp.searchsorted(se, jnp.arange(e_loc), side="left")
        slot2 = jnp.arange(R * cap) - seg2[jnp.minimum(se, e_loc - 1)]
        slot2_c = jnp.where(slot2 < cap2, slot2, cap2)

        buf = jnp.zeros((e_loc, cap2 + 1, d_), xs.dtype)
        buf = buf.at[se, slot2_c].set(rx[order2], mode="drop")  # se=e_loc drops
        hidden = buf[:, :cap2]

        act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", hidden, wg,
                                     preferred_element_type=jnp.float32))
        act = act * jnp.einsum("ecd,edf->ecf", hidden, wu,
                               preferred_element_type=jnp.float32)
        out = jnp.einsum("ecf,efd->ecd", act.astype(xs.dtype), wd,
                         preferred_element_type=jnp.float32)  # partial (F-shard)

        # un-group, reduce the F-shards in bf16, send back
        out_pad = jnp.zeros((e_loc + 1, cap2 + 1, d_), xs.dtype)
        out_pad = out_pad.at[:e_loc, :cap2].set(out.astype(xs.dtype))
        rows_sorted = out_pad[jnp.minimum(se, e_loc), slot2_c]
        rows = jnp.zeros((R * cap, d_), xs.dtype).at[order2].set(rows_sorted)
        rows = jax.lax.psum(rows, model_axis)
        ret = jax.lax.all_to_all(rows.reshape(R, cap, d_), data_axis, 0, 0)

        # ---- weighted combine at the source
        ret_pad = jnp.concatenate(
            [ret, jnp.zeros((R, 1, d_), ret.dtype)], axis=1)
        contrib = ret_pad[sd, slot_c].astype(jnp.float32)
        ws = w_flat[order][:, None]
        y = jnp.zeros((nn, d_), jnp.float32).at[st].add(contrib * ws)
        return y.reshape(b_loc, s_, d_).astype(xs.dtype), aux

    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(None, None),  # router replicated
            P(data_axis, None, model_axis),  # wg (E, D, F)
            P(data_axis, None, model_axis),  # wu
            P(data_axis, model_axis, None),  # wd (E, F, D)
            P(dp, None, None),  # x batch-sharded
        ),
        out_specs=(P(dp, None, None), P()),
        check_vma=False,
    )(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    return y, aux


def apply_auto(p, x, cfg: MoEConfig):
    """Pick the EP shard_map path when deployed with an expert axis."""
    if cfg.ep_axis is not None and _EP_MESH is not None:
        return apply_expert_parallel(p, x, cfg)
    return apply(p, x, cfg)


def apply_reference(p, x, cfg: MoEConfig):
    """O(E·N) oracle: every expert on every token, masked combine.

    Used only in tests to validate the sort-based dispatch (drops aside).
    """
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    logits = xt.astype(jnp.float32) @ p["router"]
    if cfg.router_softcap:
        logits = cfg.router_softcap * jnp.tanh(logits / cfg.router_softcap)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_ids = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    def expert(eidx):
        act = jax.nn.silu(xt @ p["w_gate"][eidx]) * (xt @ p["w_up"][eidx])
        return act @ p["w_down"][eidx]  # (N, D)

    all_out = jax.vmap(expert)(jnp.arange(cfg.num_experts))  # (E, N, D)
    w_full = jnp.zeros((xt.shape[0], cfg.num_experts), jnp.float32)
    w_full = jax.vmap(lambda w, i, row: row.at[i].set(w))(top_w, top_ids, w_full)
    y = jnp.einsum("ne,end->nd", w_full, all_out.astype(jnp.float32))
    return y.reshape(b, s, d).astype(x.dtype)
