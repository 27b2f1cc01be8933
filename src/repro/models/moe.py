"""Mixture-of-Experts layer: routed top-k experts + optional shared experts.

Three interchangeable implementations (cfg.moe.impl):

  dense   -- every expert on every token, gate-combined.  O(E/k) FLOP
             overhead; used as the correctness oracle and for tiny configs.
  ragged  -- tokens sorted by expert id, grouped GEMM via jax.lax.ragged_dot.
             Exact FLOPs; the single-device / auto-sharded path.
  ep      -- expert parallelism: shard_map over the ("pod","data") mesh axes
             with capacity-bounded all_to_all dispatch/combine, local experts
             computed with ragged_dot, TP (f over "model") with a single psum
             per layer.  Experts with E < n_shards are replicated R = shards/E
             times (grok: 8 experts over 16 shards -> R=2); replica gradients
             are symmetrized in the train step.

The routed output is combined with the shared-expert output (computed by the
caller as a dense FFN under auto sharding) and carries a load-balance aux
loss (switch-transformer style).

A replica may hold a share of the experts (``MoEConfig.n_held`` from
``held_start``: one rank of an expert-parallel deployment, without its
exchange).  The router scores all experts; dense, ragged and gather
compute the held experts' part of the result, and pairs routed to experts
held elsewhere add nothing.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.distributed import context as dist_ctx
from repro.models import ops


def _router(p: Dict, x: jax.Array, cfg: ModelConfig):
    """x [T,d] -> (probs [T,E] f32, topk_idx [T,k], topk_w [T,k] f32),
    over all E experts whichever this replica holds."""
    m = cfg.moe
    logits = (x.astype(jnp.float32) @ p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_idx = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk:
        topk_w = topk_w / jnp.maximum(jnp.sum(topk_w, -1, keepdims=True),
                                      1e-9)
    else:
        topk_w = topk_w * m.routed_scaling
    return probs, topk_idx, topk_w


def _held_pairs(cfg: ModelConfig, topk_idx: jax.Array, topk_w: jax.Array):
    """Each (token, expert) pair as this replica's experts see it: the
    index among the held experts and the weight.  A pair routed to an
    expert held elsewhere gets index ``held`` (one past the last) and
    weight 0: it adds nothing here."""
    m = cfg.moe
    if m.held == m.n_experts:
        return topk_idx, topk_w
    local = topk_idx - m.held_start
    here = (local >= 0) & (local < m.held)
    return (jnp.where(here, local, m.held),
            jnp.where(here, topk_w, 0.0))


def held_counts(p: Dict, cfg: ModelConfig, x_flat: jax.Array) -> jax.Array:
    """Tokens of ``x_flat`` [T,d] routed to each held expert: int32
    [held]."""
    _, topk_idx, topk_w = _router(p, x_flat, cfg)
    local, _ = _held_pairs(cfg, topk_idx, topk_w)
    return jnp.bincount(local.reshape(-1), length=cfg.moe.held
                        ).astype(jnp.int32)


def aux_loss(probs: jax.Array, topk_idx: jax.Array, n_experts: int):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    t = probs.shape[0]
    sel = jax.nn.one_hot(topk_idx, n_experts, dtype=jnp.float32)  # [T,k,E]
    f = jnp.mean(jnp.sum(sel, axis=1), axis=0)      # fraction routed to e * k
    p_mean = jnp.mean(probs, axis=0)
    return n_experts * jnp.sum(f * p_mean) / topk_idx.shape[1]


def _unique_experts(w: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Strip EP replication from stored expert weights (for non-EP math)."""
    e = cfg.moe.held
    if w.shape[0] == e:
        return w
    r = w.shape[0] // e
    return w[::r]


def _expert_ffn_dense(p: Dict, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """All experts on all tokens: x [T,d] -> [T,E,d]."""
    act = ops.activation(cfg.activation)
    w_up = _unique_experts(p["w_up"], cfg)
    w_down = _unique_experts(p["w_down"], cfg)
    h = jnp.einsum("td,edf->tef", x, w_up)
    if cfg.gated_mlp:
        g = jnp.einsum("td,edf->tef", x, _unique_experts(p["w_gate"], cfg))
        h = act(g) * h
    else:
        h = act(h)
    return jnp.einsum("tef,efd->ted", h, w_down)


def moe_dense(p: Dict, cfg: ModelConfig, x_flat: jax.Array):
    probs, topk_idx, topk_w = _router(p, x_flat, cfg)
    local, w = _held_pairs(cfg, topk_idx, topk_w)
    y_all = _expert_ffn_dense(p["routed"], cfg, x_flat)     # [T,held,d]
    # scatter topk weights into [T,held]; pairs held elsewhere drop out
    comb = jnp.zeros((x_flat.shape[0], cfg.moe.held), jnp.float32)
    comb = comb.at[jnp.arange(x_flat.shape[0])[:, None],
                   local].add(w, mode="drop")
    y = jnp.einsum("te,ted->td", comb.astype(x_flat.dtype), y_all)
    return y, aux_loss(probs, topk_idx, cfg.moe.n_experts)


def _grouped_ffn(p: Dict, cfg: ModelConfig, x_sorted: jax.Array,
                 group_sizes: jax.Array) -> jax.Array:
    """Grouped GEMM over experts: x_sorted [T,d] grouped by expert."""
    act = ops.activation(cfg.activation)
    w_up = _unique_experts(p["w_up"], cfg)
    w_down = _unique_experts(p["w_down"], cfg)
    h = jax.lax.ragged_dot(x_sorted, w_up, group_sizes)
    if cfg.gated_mlp:
        g = jax.lax.ragged_dot(x_sorted,
                               _unique_experts(p["w_gate"], cfg), group_sizes)
        h = act(g) * h
    else:
        h = act(h)
    return jax.lax.ragged_dot(h, w_down, group_sizes)


def moe_ragged(p: Dict, cfg: ModelConfig, x_flat: jax.Array):
    """Sort-by-expert + ragged_dot grouped GEMM (exact FLOPs)."""
    m = cfg.moe
    t = x_flat.shape[0]
    probs, topk_idx, topk_w = _router(p, x_flat, cfg)
    local, w = _held_pairs(cfg, topk_idx, topk_w)
    # pairs held elsewhere sort last, past every group (masked below)
    flat_expert = local.reshape(-1)                         # [T*k]
    order = jnp.argsort(flat_expert)
    token_of_pair = jnp.arange(t * m.top_k) // m.top_k
    x_sorted = x_flat[token_of_pair[order]]
    group_sizes = jnp.bincount(flat_expert, length=m.held)
    y_sorted = _grouped_ffn(p["routed"], cfg, x_sorted, group_sizes)
    # unsort and weighted-combine the k copies
    inv = jnp.argsort(order)
    y_pairs = y_sorted[inv].reshape(t, m.top_k, -1)
    if m.held != m.n_experts:
        # what ragged_dot leaves in rows past the groups is not defined
        # on every backend: select, as 0 * NaN would not be 0
        y_pairs = jnp.where((local < m.held)[..., None], y_pairs, 0)
    y = jnp.sum(y_pairs * w[..., None].astype(y_pairs.dtype), axis=1)
    return y.astype(x_flat.dtype), aux_loss(probs, topk_idx, m.n_experts)


# ---------------------------------------------------------------------------
# Expert-parallel path (shard_map + all_to_all)
# ---------------------------------------------------------------------------

def _ep_local(x_local, router_w, w_gate, w_up, w_down, *, cfg: ModelConfig,
              n_shards: int, ep_axes, tp_axis: str, aux_axes=None):
    """Body run per device group.  x_local [T_loc, d]; expert weights are the
    local slices [e_loc, d, f_loc] / [e_loc, f_loc, d]."""
    m = cfg.moe
    e, k = m.n_experts, m.top_k
    t_loc, d = x_local.shape
    r = max(1, n_shards // e)               # replication factor
    e_loc = max(1, e // n_shards)           # experts per device

    probs, topk_idx, topk_w = _router({"router": router_w}, x_local, cfg)
    pair_token = jnp.arange(t_loc * k) // k
    pair_expert = topk_idx.reshape(-1)
    pair_w = topk_w.reshape(-1)
    # destination device: spread across the R replicas of the expert
    if r > 1:
        dest = pair_expert * r + (pair_token % r)
    else:
        dest = pair_expert // e_loc
    # capacity per destination
    cap = int(-(-t_loc * k // n_shards) * m.capacity_factor)
    cap = max(8, -(-cap // 8) * 8)
    onehot = jax.nn.one_hot(dest, n_shards, dtype=jnp.int32)     # [P,S]
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_in_dest = jnp.sum(pos * onehot, axis=1)                  # [P]
    keep = pos_in_dest < cap
    # send buffers
    send_x = jnp.zeros((n_shards, cap, d), x_local.dtype)
    send_eid = jnp.zeros((n_shards, cap), jnp.int32)
    di, pi = dest, jnp.where(keep, pos_in_dest, cap)  # cap row -> dropped
    send_x = send_x.at[di, pi].set(x_local[pair_token], mode="drop")
    send_eid = send_eid.at[di, pi].set(pair_expert % e_loc if e_loc > 1
                                       else 0, mode="drop")
    recv_x = jax.lax.all_to_all(send_x, ep_axes, 0, 0, tiled=True)
    recv_eid = jax.lax.all_to_all(send_eid, ep_axes, 0, 0, tiled=True)
    rx = recv_x.reshape(n_shards * cap, d)
    reid = recv_eid.reshape(-1)
    if e_loc > 1:
        order = jnp.argsort(reid)
        rx_sorted = rx[order]
        group_sizes = jnp.bincount(reid, length=e_loc)
        inv = jnp.argsort(order)
    else:
        rx_sorted = rx
        group_sizes = jnp.array([n_shards * cap], jnp.int32)
        inv = None
    act = ops.activation(cfg.activation)
    h = jax.lax.ragged_dot(rx_sorted, w_up, group_sizes)
    if cfg.gated_mlp:
        h = act(jax.lax.ragged_dot(rx_sorted, w_gate, group_sizes)) * h
    else:
        h = act(h)
    y_sorted = jax.lax.ragged_dot(h, w_down, group_sizes)
    y_sorted = jax.lax.psum(y_sorted, tp_axis)       # TP reduce over f
    y_loc = y_sorted if inv is None else y_sorted[inv]
    y_back = jax.lax.all_to_all(y_loc.reshape(n_shards, cap, d),
                                ep_axes, 0, 0, tiled=True)
    # gather each pair's result and combine
    y_pairs = y_back[di, pi] * keep[:, None].astype(y_back.dtype)
    y = jnp.zeros((t_loc, d), jnp.float32)
    y = y.at[pair_token].add(
        (y_pairs * pair_w[:, None].astype(y_pairs.dtype)).astype(jnp.float32))
    aux_axes = aux_axes or ep_axes
    aux = jax.lax.psum(aux_loss(probs, topk_idx, e), aux_axes)
    aux = aux / jax.lax.psum(jnp.ones(()), aux_axes)
    return y.astype(x_local.dtype), aux


def moe_ep(p: Dict, cfg: ModelConfig, x_flat: jax.Array):
    """Expert-parallel MoE via shard_map over the ambient mesh.

    Dispatch (all_to_all) runs over ``ctx.ep_axes`` (the within-pod "data"
    axis); tokens arrive sharded over ``ctx.batch_axes`` (which may include
    "pod": each pod then runs EP independently on replicated experts); the
    expert FFN is TP-sharded over "model" with one psum per layer.
    """
    ctx = dist_ctx.get()
    mesh = ctx.mesh
    assert mesh is not None, "EP MoE requires a parallel context mesh"
    if cfg.moe.held != cfg.moe.n_experts:
        raise ValueError("the ep path shards all experts; a replica that "
                         "holds a share runs ragged, gather or dense")
    ep_axes = ctx.ep_axes or ("data",)
    batch_axes = ctx.batch_axes or ep_axes
    tp_axis = ctx.model_axis
    n_shards = 1
    for a in ep_axes:
        n_shards *= mesh.shape[a]
    routed = p["routed"]
    e_store = routed["w_up"].shape[0]
    assert e_store % n_shards == 0, (e_store, n_shards)
    pspec = jax.sharding.PartitionSpec
    x_spec = pspec(batch_axes, None)
    w3 = pspec(ep_axes, None, tp_axis)
    w3d = pspec(ep_axes, tp_axis, None)
    fn = functools.partial(_ep_local, cfg=cfg, n_shards=n_shards,
                           ep_axes=ep_axes, tp_axis=tp_axis,
                           aux_axes=batch_axes)
    y, aux = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(x_spec, pspec(None, None), w3, w3, w3d),
        out_specs=(x_spec, pspec()),
        check_vma=False,
    )(x_flat, p["router"],
      routed.get("w_gate", routed["w_up"]), routed["w_up"], routed["w_down"])
    return y, aux


def moe_gather(p: Dict, cfg: ModelConfig, x_flat: jax.Array):
    """Tiny-batch path (e.g. batch-1 long-context decode): dynamically
    gather only the top-k experts' weights instead of computing or
    gathering all E experts."""
    m = cfg.moe
    t = x_flat.shape[0]
    probs, topk_idx, topk_w = _router(p, x_flat, cfg)
    # a pair held elsewhere reads the last held expert, at weight 0
    local, w = _held_pairs(cfg, topk_idx, topk_w)
    act = ops.activation(cfg.activation)
    w_up = _unique_experts(p["routed"]["w_up"], cfg)
    w_down = _unique_experts(p["routed"]["w_down"], cfg)
    w_gate = _unique_experts(p["routed"].get("w_gate",
                                             p["routed"]["w_up"]), cfg)
    wu = jnp.take(w_up, local, axis=0, mode="clip")      # [T,k,d,f]
    wd = jnp.take(w_down, local, axis=0, mode="clip")    # [T,k,f,d]
    h = jnp.einsum("td,tkdf->tkf", x_flat, wu)
    if cfg.gated_mlp:
        wg = jnp.take(w_gate, local, axis=0, mode="clip")
        h = act(jnp.einsum("td,tkdf->tkf", x_flat, wg)) * h
    else:
        h = act(h)
    y = jnp.einsum("tkf,tkfd->tkd", h, wd)
    y = jnp.sum(y * w[..., None].astype(y.dtype), axis=1)
    return y.astype(x_flat.dtype), aux_loss(probs, topk_idx, m.n_experts)


def moe_layer(p: Dict, cfg: ModelConfig, x: jax.Array) -> Tuple[jax.Array,
                                                                jax.Array]:
    """Full MoE block.  x [B,S,d] -> (y [B,S,d], aux_loss scalar)."""
    m = cfg.moe
    shape = x.shape
    x_flat = x.reshape(-1, shape[-1])
    impl = m.impl
    if impl == "ep":
        ctx = dist_ctx.get()
        if ctx.mesh is None:
            impl = "ragged"
        else:
            shards = 1
            for a in (ctx.batch_axes or ctx.ep_axes):
                shards *= ctx.mesh.shape[a]
            if x_flat.shape[0] % shards != 0 or x_flat.shape[0] < shards:
                impl = "gather"     # e.g. single-token long-context decode
    if x_flat.shape[0] <= 8 and impl != "ep":
        impl = "gather"
    if impl == "dense":
        y, aux = moe_dense(p, cfg, x_flat)
    elif impl == "ragged":
        y, aux = moe_ragged(p, cfg, x_flat)
    elif impl == "gather":
        y, aux = moe_gather(p, cfg, x_flat)
    elif impl == "ep":
        y, aux = moe_ep(p, cfg, x_flat)
    else:
        raise ValueError(impl)
    if m.n_shared:
        from repro.models.model import ffn_forward
        y = y + ffn_forward(p["shared"], cfg, x_flat)
    return y.reshape(shape), aux
