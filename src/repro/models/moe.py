"""Mixture-of-Experts FFN with expert parallelism over the 'model' axis.

Design (recorded in DESIGN.md): experts are sharded over the TP axis
(E_local = E / tp).  Each (dp, tp) device routes its *data shard's* tokens,
gathers the ones bound for its local experts into a capacity-bounded
(E_local, C, D) buffer, runs the expert GEMMs, scatters back, and psums the
partial outputs over 'model'.  Compared to an all_to_all dispatch this
trades duplicated (cheap) routing math for:
  * exactly ONE collective per MoE layer — the same (B,S,D) psum a
    row-parallel matmul would issue anyway;
  * no divisibility constraints on S (works for decode S=1);
  * capacity-dropping only at the per-expert level (standard GShard-style).
The a2a variant is a recorded §Perf candidate.

This is also the one place the paper's vocabulary genuinely maps onto MoE:
dispatch is a giant sparse matrix application (CoordinateMatrix semantics),
implemented the TPU way — sort + dense segment GEMMs instead of shuffles.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .layers import _dense_init, pdtype
from .sharding import batch_axes, current_mesh

Array = jax.Array


def init_moe(key, cfg: ModelConfig):
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_ff_expert, m.num_experts
    dt = pdtype(cfg)
    ks = jax.random.split(key, 7)
    p = {
        "router": _dense_init(ks[0], (d, E), jnp.float32),
        "w_gate": _dense_init(ks[1], (E, d, f), dt, scale=1.0 / (d ** 0.5)),
        "w_up": _dense_init(ks[2], (E, d, f), dt, scale=1.0 / (d ** 0.5)),
        "w_down": _dense_init(ks[3], (E, f, d), dt, scale=1.0 / (f ** 0.5)),
    }
    if cfg.moe_2d:
        # §Perf: experts resident 2D-sharded (E over model, F over data):
        # decode then never re-gathers weights — see apply_moe.
        ba = batch_axes()
        s = {
            "router": P(None, None),
            "w_gate": P("model", None, ba),
            "w_up": P("model", None, ba),
            "w_down": P("model", ba, None),
        }
    else:
        s = {
            "router": P(None, None),
            "w_gate": P("model", None, None),
            "w_up": P("model", None, None),
            "w_down": P("model", None, None),
        }
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        p |= {"ws_gate": _dense_init(ks[4], (d, fs), dt),
              "ws_up": _dense_init(ks[5], (d, fs), dt),
              "ws_down": _dense_init(ks[6], (fs, d), dt)}
        s |= {"ws_gate": P(None, "model"), "ws_up": P(None, "model"),
              "ws_down": P("model", None)}
    return p, s


def _moe_local(xt: Array, p: dict, cfg: ModelConfig, e_start: Array,
               e_local: int, capacity: int):
    """Token dispatch + expert GEMMs for this device's expert slice.
    xt: (T, D) local tokens.  Returns (partial output (T, D), aux loss)."""
    m = cfg.moe
    T, D = xt.shape
    E, k = m.num_experts, m.top_k

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = jax.lax.top_k(probs, k)                    # (T, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # Load-balancing auxiliary loss (Switch-style): E · Σ_e f_e · P_e
    counts = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0)
    f_e = counts / (T * k)
    P_e = probs.mean(0)
    aux = E * jnp.sum(f_e * P_e)

    N = T * k
    flat_e = eidx.reshape(-1)
    flat_g = gates.reshape(-1).astype(xt.dtype)
    flat_t = jnp.arange(N, dtype=jnp.int32) // k

    local = (flat_e >= e_start) & (flat_e < e_start + e_local)
    le = jnp.where(local, flat_e - e_start, e_local)         # e_local=trash
    perm = jnp.argsort(le, stable=True)
    sorted_le = le[perm]
    first = jnp.searchsorted(sorted_le, jnp.arange(e_local + 1),
                             side="left")
    pos = jnp.arange(N, dtype=jnp.int32) - first[sorted_le]
    keep = (sorted_le < e_local) & (pos < capacity)
    slot = jnp.where(keep, sorted_le * capacity + pos, e_local * capacity)

    # Slot-centric dispatch: build the small slot→token map first so the
    # only D-wide tensors are slot-sized (E_l·C, D), never (T·k, D).
    n_slots = e_local * capacity
    slot_token = jnp.zeros((n_slots + 1,), jnp.int32).at[slot].set(
        flat_t[perm])[:-1]
    slot_gate = jnp.zeros((n_slots + 1,), xt.dtype).at[slot].set(
        jnp.where(keep, flat_g[perm], 0))[:-1]
    slot_valid = jnp.zeros((n_slots + 1,), jnp.bool_).at[slot].set(
        keep)[:-1]

    disp = jnp.where(slot_valid[:, None], xt[slot_token], 0)
    disp = disp.reshape(e_local, capacity, D)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, p["w_gate"])) * \
        jnp.einsum("ecd,edf->ecf", disp, p["w_up"])
    out_e = jnp.einsum("ecf,efd->ecd", h, p["w_down"])       # (E_l, C, D)

    out = jnp.zeros((T, D), xt.dtype).at[slot_token].add(
        out_e.reshape(n_slots, D) * slot_gate[:, None])
    return out, aux


def apply_moe(p, x: Array, cfg: ModelConfig):
    """x: (B, S, D) sharded over batch axes.  Returns (out, aux_loss)."""
    m = cfg.moe
    mesh = current_mesh()
    B, S, D = x.shape

    if mesh is None:
        # Single-device path (smoke tests): one "shard" holding all experts.
        xt = x.reshape(B * S, D)
        cap = max(int(B * S * m.top_k * m.capacity_factor / m.num_experts), 4)
        out, aux = _moe_local(xt, p, cfg, jnp.int32(0), m.num_experts, cap)
        out = out.reshape(B, S, D)
    else:
        dp = batch_axes(mesh)
        tp = mesh.shape["model"]
        e_local = m.num_experts // tp
        dp_size = 1
        for a in dp:
            dp_size *= mesh.shape[a]
        t_loc = (B // dp_size) * S
        cap = max(int(t_loc * m.top_k * m.capacity_factor / m.num_experts), 4)

        if cfg.moe_2d:
            # Token-gather + F-sharded expert compute: moves T·D activations
            # instead of E·D·F weights — the decode-side win (§Perf).
            t_all = B * S
            cap2 = max(int(t_all * m.top_k * m.capacity_factor
                           / m.num_experts), 4)

            def body2(x_loc, router, wg, wu, wd):
                e_start = jax.lax.axis_index("model") * e_local
                pl = {"router": router, "w_gate": wg, "w_up": wu,
                      "w_down": wd}
                xt = jax.lax.all_gather(x_loc.reshape(-1, D), dp, axis=0,
                                        tiled=True)          # (T_all, D)
                out_all, aux = _moe_local(xt, pl, cfg, e_start, e_local,
                                          cap2)
                out_all = jax.lax.psum(out_all, ("model", *dp))
                idx = jnp.int32(0)
                for a in dp:
                    idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
                t_loc = x_loc.shape[0] * S
                out = jax.lax.dynamic_slice_in_dim(out_all, idx * t_loc,
                                                   t_loc)
                return out.reshape(x_loc.shape), aux

            g_spec = P("model", None, dp)
            d_spec = P("model", dp, None)
            out, aux = jax.shard_map(
                body2, mesh=mesh,
                in_specs=(P(dp, None, None), P(None, None), g_spec, g_spec,
                          d_spec),
                out_specs=(P(dp, None, None), P()),
                check_vma=False,
            )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])
        else:
            def body(x_loc, router, wg, wu, wd):
                e_start = jax.lax.axis_index("model") * e_local
                pl = {"router": router, "w_gate": wg, "w_up": wu,
                      "w_down": wd}
                xt = x_loc.reshape(-1, D)
                out, aux = _moe_local(xt, pl, cfg, e_start, e_local, cap)
                out = jax.lax.psum(out, "model")
                aux = jax.lax.pmean(aux, dp)
                return out.reshape(x_loc.shape), aux

            espec = P("model", None, None)
            out, aux = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(dp, None, None), P(None, None), espec, espec,
                          espec),
                out_specs=(P(dp, None, None), P()),
                check_vma=False,
            )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.num_shared_experts:
        h = jax.nn.silu(x @ p["ws_gate"]) * (x @ p["ws_up"])
        out = out + h @ p["ws_down"]
    return out, aux * m.router_aux_loss
