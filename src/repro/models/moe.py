"""Mixture-of-Experts FFN with sort-based capacity dispatch.

TPU adaptation: instead of a one-hot dispatch einsum (O(T·E·C·D) FLOPs) or a
megablocks-style CUDA grouped GEMM, tokens are sorted by expert id and
gathered into a capacity-bounded [E, C, D] buffer (sharded expert→'model',
EP). The per-expert FFN is a single batched einsum that the MXU executes at
full tilt; combine is a scatter-add weighted by the router gates. Dropped
tokens (capacity overflow) pass through the residual, standard for
capacity-based MoE.

Router top-k metadata is exactly the paper's "header" traffic: a few int32s
per token steering where the bulk activation payload is processed.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.sharding import constrain
from repro.common.types import ModelConfig
from repro.models.layers import ParamSpec


def moe_template(cfg: ModelConfig) -> Dict:
    e = cfg.padded_experts
    t: Dict = {
        "router": ParamSpec((cfg.d_model, e), (None, None)),  # tiny; replicated
        "w_gate": ParamSpec((e, cfg.d_model, cfg.expert_d_ff), ("expert", "fsdp", "tensor")),
        "w_up": ParamSpec((e, cfg.d_model, cfg.expert_d_ff), ("expert", "fsdp", "tensor")),
        "w_down": ParamSpec((e, cfg.expert_d_ff, cfg.d_model), ("expert", "tensor", "fsdp")),
    }
    if cfg.num_shared_experts:
        sf = cfg.num_shared_experts * cfg.expert_d_ff
        t["shared_gate"] = ParamSpec((cfg.d_model, sf), ("fsdp", "tensor"))
        t["shared_up"] = ParamSpec((cfg.d_model, sf), ("fsdp", "tensor"))
        t["shared_down"] = ParamSpec((sf, cfg.d_model), ("tensor", "fsdp"))
        t["shared_gate_proj"] = ParamSpec((cfg.d_model, 1), ("fsdp", None))
    return t


def router_topk(
    logits: jax.Array, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """[T, E_padded] -> (gates [T,k], expert_ids [T,k]). Padded experts are
    masked out before top-k so they can never be selected."""
    if cfg.padded_experts > cfg.num_experts:
        pad_mask = jnp.arange(cfg.padded_experts) >= cfg.num_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    return gates, ids


def aux_load_balance_loss(logits: jax.Array, ids: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Switch-style load-balancing auxiliary loss."""
    e = cfg.num_experts
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)[..., :e]
    me = jnp.mean(probs, axis=0)
    one_hot = jax.nn.one_hot(ids, cfg.padded_experts, dtype=jnp.float32)[..., :e]
    ce = jnp.mean(jnp.sum(one_hot, axis=1), axis=0) / cfg.top_k
    return e * jnp.sum(me * ce)


def _dispatch_compute_combine(x, p_gate, p_up, p_down, gates, ids, cfg,
                              capacity_factor: float, e_first: int, e_count: int):
    """Sort-based dispatch for experts [e_first, e_first+e_count) over local
    tokens x [T, D]; returns the weighted combined output [T, D]."""
    t, d = x.shape
    k = cfg.top_k
    e_total = cfg.padded_experts
    cap = max(8, int(math.ceil(t * k / e_total * capacity_factor)))
    cap = min(cap, t)

    flat_e = ids.reshape(-1)                       # [T*k] global expert ids
    order = jnp.argsort(flat_e)
    se = flat_e[order]
    first = jnp.searchsorted(se, jnp.arange(e_total), side="left")
    pos = jnp.arange(t * k) - first[se]            # position within expert group
    token_idx = order // k
    local_e = se - e_first
    mine = (local_e >= 0) & (local_e < e_count) & (pos < cap)
    dst = jnp.where(mine, local_e * cap + pos, e_count * cap)  # OOB -> dropped

    buf = jnp.zeros((e_count * cap, d), x.dtype)
    buf = buf.at[dst].set(x[token_idx], mode="drop")
    buf = buf.reshape(e_count, cap, d)

    h = jnp.einsum("ecd,edf->ecf", buf, p_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, p_up)
    h = jax.nn.silu(h) * u
    out_e = jnp.einsum("ecf,efd->ecd", h, p_down).reshape(e_count * cap, d)

    contrib = jnp.where(mine[:, None],
                        out_e[jnp.clip(dst, 0, e_count * cap - 1)], 0.0)
    gate_per = gates.reshape(-1)[order][:, None].astype(x.dtype)
    return jnp.zeros((t, d), x.dtype).at[token_idx].add(contrib * gate_per)


def _shared_expert(p, x):
    sh = jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])
    sg = jax.nn.sigmoid(x @ p["shared_gate_proj"])
    return sg * (sh @ p["shared_down"])


def moe_ffn(
    p: Dict,
    x: jax.Array,
    cfg: ModelConfig,
    *,
    capacity_factor: float = 1.25,
    return_aux: bool = False,
):
    """x [T, D] -> [T, D].

    Expert-parallel path (shard_map): activations are batch-sharded over the
    data axes and replicated over 'model'; experts are sharded over 'model'.
    Dispatch to the local experts is therefore a LOCAL gather (zero
    communication — the Libra selective-copy idea applied to MoE routing:
    the router's top-k ids are the metadata; token payloads never move), and
    the combine is one psum over 'model', the same collective a dense TP FFN
    pays. FSDP weight shards are all-gathered over 'data' per layer.
    """
    from repro.common.sharding import _current_mesh

    mesh = _current_mesh()
    e = cfg.padded_experts
    use_ep = (
        mesh is not None
        and "model" in mesh.axis_names
        and dict(mesh.shape)["model"] > 1
        and e % dict(mesh.shape)["model"] == 0
    )

    if not use_ep:
        logits = x @ p["router"]
        gates, ids = router_topk(logits, cfg)
        out = _dispatch_compute_combine(x, p["w_gate"], p["w_up"], p["w_down"],
                                        gates, ids, cfg, capacity_factor, 0, e)
        if cfg.num_shared_experts:
            out = out + _shared_expert(p, x)
        if return_aux:
            return out, aux_load_balance_loss(logits, ids, cfg)
        return out

    sizes = dict(mesh.shape)
    m_size = sizes["model"]
    e_local = e // m_size
    data_axes = tuple(a for a in ("pod", "data") if a in sizes)
    t = x.shape[0]
    dshard = data_axes if len(data_axes) > 1 else (data_axes[0] if data_axes else None)
    x_spec = P(dshard) if (dshard and t % math.prod(
        [sizes[a] for a in (data_axes or ())]) == 0) else P(None)
    # weight specs mirror the declared param sharding (expert->model, fsdp->data)
    w_spec = P("model", "data" if "data" in sizes else None, None)
    wd_spec = P("model", None, "data" if "data" in sizes else None)

    def body(x, router, wg, wu, wd, shared):
        if "data" in sizes:
            wg = jax.lax.all_gather(wg, "data", axis=1, tiled=True)
            wu = jax.lax.all_gather(wu, "data", axis=1, tiled=True)
            wd = jax.lax.all_gather(wd, "data", axis=2, tiled=True)
        logits = x @ router
        gates, ids = router_topk(logits, cfg)
        e_first = jax.lax.axis_index("model") * e_local
        partial = _dispatch_compute_combine(x, wg, wu, wd, gates, ids, cfg,
                                            capacity_factor, e_first, e_local)
        aux = aux_load_balance_loss(logits, ids, cfg)
        if shared is not None:
            # shared-expert FFN is TP-sharded over 'model' (sf dim): its
            # contribution is partial over 'model' too — fold into one psum.
            sg, su, sd, sgp = shared
            if "data" in sizes:
                sg = jax.lax.all_gather(sg, "data", axis=0, tiled=True)
                su = jax.lax.all_gather(su, "data", axis=0, tiled=True)
                sd = jax.lax.all_gather(sd, "data", axis=1, tiled=True)
            sh = jax.nn.silu(x @ sg) * (x @ su)
            gate = jax.nn.sigmoid(x @ sgp)
            partial = partial + gate * (sh @ sd)
        # combine in bf16: halves the dominant collective (hillclimb #3;
        # same as the TP-reduce precision production frameworks use)
        out = jax.lax.psum(partial.astype(x.dtype), "model")
        return out, aux

    shared = None
    shared_specs = None
    if cfg.num_shared_experts:
        sf_spec = P("data" if "data" in sizes else None, "model")
        shared = (p["shared_gate"], p["shared_up"], p["shared_down"],
                  p["shared_gate_proj"])
        shared_specs = (sf_spec, sf_spec, P("model", "data" if "data" in sizes
                                            else None), P(None, None))

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, wd_spec, shared_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"], shared)

    if return_aux:
        return out, aux
    return out
