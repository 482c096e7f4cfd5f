"""Expert-parallel MoE with EXPLICIT all-to-all dispatch (shard_map).

The GSPMD path (models/moe.py) lets XLA infer collectives from sharded
einsums. This module expresses the canonical two-hop expert-parallel
schedule by hand, the way Megatron/DeepSpeed structure it:

  tokens sharded over the 'model' axis (each shard owns n/S tokens) →
  route locally → pack per-destination-shard slabs → all_to_all →
  second-stage dispatch to the shard's local experts → grouped FFN →
  inverse scatter → all_to_all back → weighted combine at the source.

`shard_map(..., axis_names={'model'})` manualizes ONLY the model axis: the
batch stays auto-sharded over 'data'/'pod' by GSPMD around it. The router
is replicated; each shard routes its own token slice, so no compute is
duplicated and every token is owned by exactly one shard.

Numerically equivalent to models/moe.apply_moe up to capacity policy
(stage-1 capacity is per destination shard, not per expert) — the
equivalence test uses generous capacity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import apply_mlp


def _shard_map_model_axis(f, mesh, in_specs, out_specs, axis):
    """shard_map collecting over ONLY ``axis``: that axis is manualized and
    the rest is left to GSPMD."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={axis},
                         check_vma=False)


def _rank_in_bins(ids, n_bins, capacity):
    """Stable-sort ids into bins, rank within bin, drop beyond capacity.
    Returns (order, bin_idx, rank_idx) where dropped entries map to the
    dummy bin `n_bins` / rank 0."""
    order = jnp.argsort(ids, stable=True)
    ids_s = ids[order]
    starts = jnp.searchsorted(ids_s, jnp.arange(n_bins), side="left")
    rank = jnp.arange(ids.shape[0]) - starts[jnp.clip(ids_s, 0, n_bins - 1)]
    keep = (rank < capacity) & (ids_s < n_bins)
    return order, jnp.where(keep, ids_s, n_bins), jnp.where(keep, rank, 0)


def _table(order, b_idx, r_idx, payload, n_bins, capacity, fill):
    return jnp.full((n_bins + 1, capacity), fill, payload.dtype) \
        .at[b_idx, r_idx].set(payload[order], mode="drop")[:n_bins]


def moe_all_to_all(cfg, p, x, mesh, axis="model"):
    """x (B, T, d) -> (y, aux). Requires n_experts % S == 0 and
    (B·T) % S == 0 for the mesh's model-axis size S."""
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    E, k = cfg.n_experts, cfg.top_k
    assert E % S == 0, (E, S)
    E_loc = E // S
    B, T, d = x.shape
    n = B * T
    assert n % S == 0, (n, S)
    n_loc = n // S
    C1 = max(int(n_loc * k / S * cfg.capacity_factor), k)   # per dest shard
    C2 = max(int(S * C1 / E_loc * cfg.capacity_factor), 1)  # per local expert

    def local(xf, router, w_gate, w_up, w_down):
        # xf (n_loc, d): this shard's tokens. experts (E_loc, ...): local.
        logits = jnp.einsum("nd,de->ne", xf.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_w, gate_e = jax.lax.top_k(probs, k)
        gate_w = gate_w / jnp.sum(gate_w, -1, keepdims=True)

        e_flat = gate_e.reshape(-1)                        # (n_loc·k,)
        t_flat = jnp.repeat(jnp.arange(n_loc), k).astype(jnp.int32)
        w_flat = gate_w.reshape(-1)
        dest = e_flat // E_loc

        # --- stage 1: pack per-destination slabs -------------------------
        order, b_idx, r_idx = _rank_in_bins(dest, S, C1)
        tok_tab = _table(order, b_idx, r_idx, t_flat, S, C1, jnp.int32(n_loc))
        eloc_tab = _table(order, b_idx, r_idx,
                          (e_flat % E_loc).astype(jnp.int32), S, C1,
                          jnp.int32(E_loc))
        w_tab = _table(order, b_idx, r_idx, w_flat, S, C1, jnp.float32(0))

        xp = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], 0)
        xsend = jnp.take(xp, tok_tab, axis=0)              # (S, C1, d)

        # --- all_to_all: slab s -> model shard s --------------------------
        xrecv = jax.lax.all_to_all(xsend, axis, 0, 0)      # (S, C1, d)
        erecv = jax.lax.all_to_all(eloc_tab[..., None], axis, 0, 0)[..., 0]

        # --- stage 2: dispatch received tokens to local experts ----------
        m = S * C1
        er = erecv.reshape(m)
        order2, b2, r2 = _rank_in_bins(er, E_loc, C2)
        slot_tab = _table(order2, b2, r2, jnp.arange(m, dtype=jnp.int32),
                          E_loc, C2, jnp.int32(m))
        xr = jnp.concatenate([xrecv.reshape(m, d),
                              jnp.zeros((1, d), xf.dtype)], 0)
        xe = jnp.take(xr, slot_tab, axis=0)                # (E_loc, C2, d)

        g = jnp.einsum("ecd,edf->ecf", xe, w_gate)
        u = jnp.einsum("ecd,edf->ecf", xe, w_up)
        h = jax.nn.silu(g.astype(jnp.float32)).astype(xe.dtype) * u
        ye = jnp.einsum("ecf,efd->ecd", h, w_down)

        # --- inverse stage 2 + return a2a + combine at source -------------
        ybuf = jnp.zeros((m + 1, d), ye.dtype) \
            .at[slot_tab.reshape(-1)].add(ye.reshape(-1, d),
                                          mode="drop")[:m]
        yback = jax.lax.all_to_all(ybuf.reshape(S, C1, d), axis, 0, 0)
        contrib = yback * w_tab[..., None].astype(yback.dtype)
        y = jnp.zeros((n_loc + 1, d), yback.dtype) \
            .at[tok_tab.reshape(-1)].add(contrib.reshape(-1, d),
                                         mode="drop")[:n_loc]

        frac_tokens = jnp.mean(
            jax.nn.one_hot(gate_e, E, dtype=jnp.float32).sum(1), axis=0)
        aux = E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0)) / k
        return y, jax.lax.pmean(aux, axis)

    fn = _shard_map_model_axis(
        local, mesh,
        in_specs=(P(axis), P(), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P()), axis=axis)
    y, aux = fn(x.reshape(n, d), p["router"], p["w_gate"], p["w_up"],
                p["w_down"])
    if cfg.n_shared_experts:
        y = y + apply_mlp(cfg, p["shared"], x.reshape(n, d))
    return y.reshape(B, T, d), aux
