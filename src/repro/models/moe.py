"""Mixture-of-Experts FFN with sort-based (gather/scatter) token dispatch.

Design: instead of the GShard one-hot dispatch tensor (O(N·E·C) memory —
prohibitive at fine-grained MoE like deepseek's 64 experts × top-6), we
route with an argsort over (expert, token) assignments:

  1. top-k gates per token → N·k (token, expert, gate) assignments;
  2. stable-sort assignments by expert; each expert's assignments form a
     contiguous run; position-in-run = index − run start (searchsorted);
  3. keep positions < capacity C, giving each kept assignment a unique
     slot in an (E·C, d) buffer (+1 overflow row for drops);
  4. gather tokens → batched expert FFN einsum over (E, C, d);
  5. scatter-add expert outputs × gates back to tokens.

All shapes static ⇒ pjit-friendly. Expert weights are sharded over the
``model`` mesh axis (expert parallelism); the gather/scatter lowers to
XLA-inserted collectives in the baseline, replaced by an explicit
shard_map all_to_all in the optimized path (see EXPERIMENTS.md §Perf).

Router: softmax gating with top-k renormalization (deepseek/dbrx style)
+ the standard auxiliary load-balancing loss (Switch-style) returned to
the caller.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from . import layers as L
from .layers import dense_init


@dataclasses.dataclass(frozen=True)
class MoEDims:
    n_experts: int
    top_k: int
    d_expert: int           # per-expert FFN hidden size
    n_shared: int = 0       # always-on shared experts (deepseek)
    capacity_factor: float = 1.25
    mlp_kind: str = "swiglu"


def init_moe(key, d_model, dims: MoEDims, dtype):
    ks = jax.random.split(key, 6)
    e, h = dims.n_experts, dims.d_expert
    p = {
        "router": dense_init(ks[0], (d_model, e), jnp.float32, scale=0.02),
        "wi": dense_init(ks[1], (e, d_model, h), dtype),
        "wo": dense_init(ks[3], (e, h, d_model), dtype),
    }
    if dims.mlp_kind in ("swiglu", "geglu"):
        p["wg"] = dense_init(ks[2], (e, d_model, h), dtype)
    if dims.n_shared:
        hs = dims.n_shared * h
        p["shared_wi"] = dense_init(ks[4], (d_model, hs), dtype)
        p["shared_wg"] = dense_init(ks[5], (d_model, hs), dtype)
        p["shared_wo"] = dense_init(
            jax.random.fold_in(ks[5], 1), (hs, d_model), dtype)
    return p


def capacity(n_tokens: int, dims: MoEDims) -> int:
    c = int(n_tokens * dims.top_k * dims.capacity_factor / dims.n_experts)
    return max(8, ((c + 7) // 8) * 8)  # pad to 8 for TPU-friendly shapes


def moe_ffn(params, x, dims: MoEDims) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) → (out (B, S, d), aux_loss scalar).

    Dispatch backend is chosen by the bound activation mesh
    (``layers.activation_mesh_scope``):

    - mesh with a ``model`` axis dividing E → :func:`moe_ffn_sharded`,
      the explicit shard_map EP path (local dispatch, psum combine);
    - otherwise → the single-device sort-based path below (smoke tests,
      CPU examples). Semantics match (tests assert allclose).
    """
    mesh = L._ACT_MESH
    if mesh is not None and "model" in mesh.shape \
            and dims.n_experts % mesh.shape["model"] == 0 \
            and mesh.shape["model"] > 1:
        return moe_ffn_sharded(params, x, dims, mesh)
    return _moe_ffn_local(params, x, dims)


def _moe_ffn_local(params, x, dims: MoEDims) -> Tuple[jax.Array, jax.Array]:
    b, s, d = x.shape
    n = b * s
    e, k = dims.n_experts, dims.top_k
    c = capacity(n, dims)
    xt = x.reshape(n, d)

    logits = (xt.astype(jnp.float32) @ params["router"])          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                 # (N, k)
    gate_vals = gate_vals / jnp.maximum(
        jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)         # renorm

    # aux load-balance loss (Switch): E * Σ_e f_e · p_e
    me = jnp.mean(probs, axis=0)
    ce = jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(
        jnp.ones((n * k,), jnp.float32)) / (n * k)
    aux = e * jnp.sum(me * ce)

    # ---- sort-based dispatch ----
    e_flat = gate_idx.reshape(-1)                                  # (N·k,)
    t_flat = jnp.repeat(jnp.arange(n), k)                          # (N·k,)
    g_flat = gate_vals.reshape(-1)
    order = jnp.argsort(e_flat, stable=True)
    e_sorted = e_flat[order]
    t_sorted = t_flat[order]
    g_sorted = g_flat[order]
    run_start = jnp.searchsorted(e_sorted, jnp.arange(e), side="left")
    pos = jnp.arange(n * k) - run_start[e_sorted]
    keep = pos < c
    slot = jnp.where(keep, e_sorted * c + pos, e * c)              # overflow

    buf = jnp.zeros((e * c + 1, d), x.dtype).at[slot].set(xt[t_sorted])
    h_in = buf[:e * c].reshape(e, c, d)

    if "wg" in params:
        act = jax.nn.silu if dims.mlp_kind == "swiglu" else jax.nn.gelu
        hmid = act(jnp.einsum("ecd,edh->ech", h_in, params["wg"])) * \
            jnp.einsum("ecd,edh->ech", h_in, params["wi"])
    else:
        hmid = jax.nn.gelu(jnp.einsum("ecd,edh->ech", h_in, params["wi"]))
    h_out = jnp.einsum("ech,ehd->ecd", hmid, params["wo"])

    flat_out = jnp.concatenate(
        [h_out.reshape(e * c, d), jnp.zeros((1, d), h_out.dtype)], axis=0)
    contrib = flat_out[slot] * g_sorted[:, None].astype(h_out.dtype)
    out = jnp.zeros((n, d), x.dtype).at[t_sorted].add(
        jnp.where(keep[:, None], contrib, 0).astype(x.dtype))

    if dims.n_shared:
        shared = (jax.nn.silu(xt @ params["shared_wg"]) *
                  (xt @ params["shared_wi"])) @ params["shared_wo"]
        out = out + shared
    return out.reshape(b, s, d), aux


# ------------------------------------------------------------------ #
# explicit expert-parallel dispatch (shard_map)
# ------------------------------------------------------------------ #
def moe_ffn_sharded(params, x, dims: MoEDims, mesh) \
        -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism with *local* dispatch.

    Key observation: activations are sharded over the ``data`` axes and
    replicated over ``model``; expert weights are sharded over ``model``
    (E_loc = E/TP experts per model rank) and replicated over data. So
    every (data i, model j) device already holds the tokens of data
    shard i AND the weights of expert group j: dispatch requires **zero
    token movement** — each device sort-selects, from its local tokens,
    the ones routed to its local experts, runs the expert FFN, and the
    per-token combine is a single ``psum`` over ``model`` (each token's
    top-k experts live on ≤k model ranks; everyone else contributes
    zeros). Under plain GSPMD the same computation lowers to
    data-dependent gathers that the partitioner can only replicate
    ("involuntary full rematerialization", ~30–170 GiB/device on the
    assigned MoE configs); the shard_map version is the TPU-native
    formulation. FSDP all-gather of the expert weights over ``data`` is
    explicit here for the same reason GSPMD would insert it.
    """
    b, s, d = x.shape
    e, k = dims.n_experts, dims.top_k
    tp = mesh.shape["model"]
    e_loc = e // tp
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    dp_size = int(np.prod([mesh.shape[a] for a in dp_axes])) \
        if dp_axes else 1
    batch_ok = dp_axes and b % max(dp_size, 1) == 0
    bspec = dp_axes if batch_ok else None

    wi = params["wi"]
    has_wg = "wg" in params
    wg = params["wg"] if has_wg else params["wi"]   # dummy slot if absent
    wo = params["wo"]
    fsdp = "data" in mesh.shape and wi.shape[1] % mesh.shape["data"] == 0

    def local_fn(x_loc, router, wi_l, wg_l, wo_l):
        bl, sl, _ = x_loc.shape
        n = bl * sl
        c = capacity(n, dims)
        xt = x_loc.reshape(n, d)
        if fsdp:  # explicit ZeRO-3 gather of this layer's expert weights
            wi_f = jax.lax.all_gather(wi_l, "data", axis=1, tiled=True)
            wo_f = jax.lax.all_gather(wo_l, "data", axis=2, tiled=True)
            wg_f = jax.lax.all_gather(wg_l, "data", axis=1, tiled=True) \
                if has_wg else None
        else:
            wi_f, wo_f = wi_l, wo_l
            wg_f = wg_l if has_wg else None

        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)

        me = jnp.mean(probs, axis=0)
        ce = jnp.zeros((e,), jnp.float32).at[gate_idx.reshape(-1)].add(
            jnp.ones((n * k,), jnp.float32)) / (n * k)
        aux = e * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, dp_axes) if dp_axes else aux

        # local sort-based dispatch restricted to this rank's experts
        e_base = jax.lax.axis_index("model") * e_loc
        e_flat = gate_idx.reshape(-1)
        t_flat = jnp.repeat(jnp.arange(n), k)
        g_flat = gate_vals.reshape(-1)
        order = jnp.argsort(e_flat, stable=True)
        e_sorted = e_flat[order]
        t_sorted = t_flat[order]
        g_sorted = g_flat[order]
        run_start = jnp.searchsorted(e_sorted, jnp.arange(e), side="left")
        pos = jnp.arange(n * k) - run_start[e_sorted]
        local = (e_sorted >= e_base) & (e_sorted < e_base + e_loc)
        keep = local & (pos < c)
        slot = jnp.where(keep, (e_sorted - e_base) * c + pos, e_loc * c)

        buf = jnp.zeros((e_loc * c + 1, d), x.dtype).at[slot].set(
            xt[t_sorted])
        h_in = buf[:e_loc * c].reshape(e_loc, c, d)
        if wg_f is not None:
            act = jax.nn.silu if dims.mlp_kind == "swiglu" else jax.nn.gelu
            hmid = act(jnp.einsum("ecd,edh->ech", h_in, wg_f)) * \
                jnp.einsum("ecd,edh->ech", h_in, wi_f)
        else:
            hmid = jax.nn.gelu(jnp.einsum("ecd,edh->ech", h_in, wi_f))
        h_out = jnp.einsum("ech,ehd->ecd", hmid, wo_f)

        flat_out = jnp.concatenate(
            [h_out.reshape(e_loc * c, d),
             jnp.zeros((1, d), h_out.dtype)], axis=0)
        contrib = flat_out[slot] * g_sorted[:, None].astype(h_out.dtype)
        out = jnp.zeros((n, d), x.dtype).at[t_sorted].add(
            jnp.where(keep[:, None], contrib, 0).astype(x.dtype))
        out = jax.lax.psum(out, "model")      # combine expert owners
        return out.reshape(bl, sl, d), aux

    wi_spec = P("model", "data" if fsdp else None, None)
    wo_spec = P("model", None, "data" if fsdp else None)
    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(bspec, None, None), P(None, None), wi_spec,
                  wi_spec, wo_spec),
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )(x, params["router"], wi, wg, wo)
    if dims.n_shared:
        xt = x.reshape(b * s, d)
        shared = (jax.nn.silu(xt @ params["shared_wg"]) *
                  (xt @ params["shared_wi"])) @ params["shared_wo"]
        out = out + shared.reshape(b, s, d)
    return out, aux
