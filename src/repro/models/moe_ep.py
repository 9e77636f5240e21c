"""Expert-parallel MoE via ``shard_map`` — the production dispatch path.

The pjit sort-based dispatch (``moe.py``) is correct but lets GSPMD invent
the communication for a global (T, D) -> (E, C, D) scatter; measured on
olmoe-1b-7b train_4k that comes out as ~15.9 TB/device/step of
replicate-and-mask all-reduces (EXPERIMENTS.md §Perf iteration 3 baseline).

Here the dataflow is explicit, mirroring the paper's content-addressed
message routing (DESIGN.md §2): tokens are *messages*, the expert id is the
*destination address*, and the mesh row delivers them:

  * tokens stay local to their ``data`` shard (replicated over ``model``);
  * every device selects, from its local tokens, the ones addressed to ITS
    experts (experts sharded over ``model``) — no dispatch communication
    at all, because token activations are already present model-wide;
  * expert weights are FSDP-sharded over ``data`` on the d_model axis and
    all-gathered per layer (training); the backward reduce-scatters —
    exactly the dense-MLP FSDP pattern;
  * combine = masked scatter-add into the local (T_loc, D) buffer followed
    by one ``psum`` over ``model`` (each token's k expert outputs live on
    <= k model shards) — the single collective of the layer.

Requires ``n_experts %% model_axis == 0`` (olmoe: 64/16; granite-moe's 40
experts are padded to 48 by ``_pad_experts`` — dummy experts receive
-inf router logits and are never selected).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.sharding.partition import current_mesh, current_rules


def _data_axes(rules) -> tuple[str, ...]:
    r = rules.get("batch", "data")
    return r if isinstance(r, tuple) else (r,)


def _fsdp_axes(rules) -> tuple[str, ...]:
    r = rules.get("embed", None)
    if r is None:
        return ()
    return r if isinstance(r, tuple) else (r,)


def padded_experts(cfg: ModelConfig, n_model: int) -> int:
    e = cfg.n_experts
    return (e + n_model - 1) // n_model * n_model


def moe_ep(params, x: jax.Array, cfg: ModelConfig):
    """Drop-in for ``moe.moe`` when a mesh with a model axis is active.
    x: (B, S, D) -> (y, aux)."""
    mesh = current_mesh()
    rules = current_rules()
    n_model = mesh.shape["model"]
    dp = _data_axes(rules)
    fsdp = _fsdp_axes(rules)
    E_pad = padded_experts(cfg, n_model)
    K = cfg.experts_per_token

    B, S, D = x.shape
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    T_loc = B * S // dp_size
    C = max(8, int(T_loc * K * cfg.capacity_factor / cfg.n_experts)
            // 8 * 8)
    E_loc = E_pad // n_model

    def pad_e(w, axis=0):
        padw = [(0, 0)] * w.ndim
        padw[axis] = (0, E_pad - cfg.n_experts)
        return jnp.pad(w, padw)

    router = pad_e(params["router"], axis=1)       # (D, E_pad)
    wi_g = pad_e(params["wi_gate"])                # (E_pad, D, F)
    wi_u = pad_e(params["wi_up"])
    wo = pad_e(params["wo"])

    in_specs = (
        P(dp, None, None),                         # x: tokens over data
        P(fsdp if fsdp else None, None),           # router
        P("model", fsdp if fsdp else None, None),  # wi_gate: EP + FSDP
        P("model", fsdp if fsdp else None, None),  # wi_up
        P("model", None, fsdp if fsdp else None),  # wo (FSDP on D out)
    )
    out_specs = (P(dp, None, None), P(), P())

    def body(x_blk, router_blk, wig_blk, wiu_blk, wo_blk):
        dtype = x_blk.dtype
        xt = x_blk.reshape(-1, D)                  # (T_loc, D)

        # FSDP all-gather of this layer's expert weights (training rules);
        # a no-op slice under the weight-stationary inference rules.
        if fsdp:
            router_full = jax.lax.all_gather(router_blk, fsdp, axis=0,
                                             tiled=True)
            wig = jax.lax.all_gather(wig_blk, fsdp, axis=1, tiled=True)
            wiu = jax.lax.all_gather(wiu_blk, fsdp, axis=1, tiled=True)
            won = jax.lax.all_gather(wo_blk, fsdp, axis=2, tiled=True)
        else:
            router_full, wig, wiu, won = (router_blk, wig_blk, wiu_blk,
                                          wo_blk)

        # ---- routing (replicated over model: every shard sees the same
        # local tokens and computes the same assignment) ---------------- #
        logits = xt.astype(jnp.float32) @ router_full.astype(jnp.float32)
        logits = jnp.where(jnp.arange(E_pad) < cfg.n_experts, logits,
                           -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, K)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E_pad,
                                     dtype=jnp.float32), axis=0)
        aux = (cfg.n_experts * jnp.sum(me * ce)
               * cfg.router_aux_weight)
        aux = jax.lax.pmean(aux, dp) if dp else aux

        # ---- select the tokens addressed to MY experts ----------------- #
        m_idx = jax.lax.axis_index("model")
        flat_e = top_e.reshape(-1)
        flat_p = top_p.reshape(-1)
        flat_tok = jnp.repeat(jnp.arange(T_loc), K)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        sorted_tok = flat_tok[order]
        seg_start = jnp.searchsorted(sorted_e, jnp.arange(E_pad),
                                     side="left")
        pos_in_e = jnp.arange(T_loc * K) - seg_start[sorted_e]
        local_e = sorted_e - m_idx * E_loc
        mine = (local_e >= 0) & (local_e < E_loc) & (pos_in_e < C)
        dropped = 1.0 - jnp.mean((pos_in_e < C).astype(jnp.float32))
        dropped = jax.lax.pmean(dropped, dp) if dp else dropped

        slot = jnp.where(mine, local_e * C + pos_in_e, E_loc * C)
        buf = jnp.zeros((E_loc * C + 1, D), dtype)
        buf = buf.at[slot].set(xt[sorted_tok])
        expert_in = buf[:-1].reshape(E_loc, C, D)

        # ---- my experts' SwiGLU (local GEMMs) --------------------------- #
        sl = lambda w: jax.lax.dynamic_slice_in_dim(
            w, m_idx * E_loc, E_loc, axis=0)
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in,
                                   sl(wig).astype(dtype)))
        h = h * jnp.einsum("ecd,edf->ecf", expert_in,
                           sl(wiu).astype(dtype))
        expert_out = jnp.einsum("ecf,efd->ecd", h, sl(won).astype(dtype))

        # ---- combine: local scatter-add + one psum over model ----------- #
        flat_out = jnp.concatenate(
            [expert_out.reshape(E_loc * C, D),
             jnp.zeros((1, D), dtype)], axis=0)
        gathered = flat_out[slot]
        w = jnp.where(mine, flat_p[order], 0.0).astype(jnp.float32)
        y = jnp.zeros((T_loc, D), jnp.float32)
        y = y.at[sorted_tok].add(gathered.astype(jnp.float32) * w[:, None])
        y = jax.lax.psum(y, "model")
        return y.reshape(x_blk.shape).astype(dtype), aux, dropped

    y, aux, dropped = jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(
        x, router, wi_g, wi_u, wo)
    return y, {"aux_loss": aux, "dropped_frac": dropped}


def moe_ep_applicable(cfg: ModelConfig) -> bool:
    mesh = current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return False
    rules = current_rules()
    dp_size = 1
    for a in _data_axes(rules):
        dp_size = dp_size * mesh.shape.get(a, 1)
    return dp_size > 1 or mesh.shape["model"] > 1
