"""Mixture-of-Experts FFN.

Two execution paths (selectable via ``cfg.moe_path``):

``tp`` (default / baseline)
    Token-choice top-k routing with *grouped local capacity*: tokens are
    reshaped to ``(n_groups, Tg)`` where ``n_groups`` aligns with the
    data-parallel sharding, so per-group gather/scatter never crosses data
    shards (the SPMD partitioner keeps them local).  Experts are evaluated by
    a ``lax.scan`` over stacked expert weights whose FFN dims are TP-sharded
    over ``model``.  The contraction over the sharded ``mlp`` dim makes XLA
    insert an all-reduce per expert — this is the honest collective-bound
    baseline that the EP path (and the §Perf hillclimb) improves on.

``ep``
    Expert parallelism via ``jax.shard_map``: the ``model`` axis owns
    ``E/tp`` experts each; tokens are sub-sliced across the model axis,
    exchanged with a single pair of ``all_to_all``s, processed by full-width
    local experts, and combined.  Collective bytes drop from
    O(E·C·D) all-reduce to O(T·k·D/tp) all-to-all per layer.

Routing is token-choice top-k with softmax-over-topk combine (qwen3 style;
top-1 degenerates to switch routing for llama4-scout).  A load-balance
auxiliary loss (Switch-style) is returned for training.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.params import ParamDef


def moe_defs(cfg, n_layers=None, stacked: bool = True):
    """Expert weights: stacked ``(E, D, F)`` for the scan path; list-of-E
    per-expert defs for the unrolled cost probe (stacked-slice grads are
    O(E²) in HLO flops — same issue as stacked layers, see transformer.py).
    """
    D, F, E = cfg.d_model, cfg.d_ff, cfg.moe.n_experts
    L = (n_layers,) if n_layers is not None else ()
    pd = ("layers",) if n_layers is not None else ()
    out = {"gate": ParamDef(L + (D, E), pd + ("embed", None), scale=0.02)}
    if stacked:
        out.update(
            w1=ParamDef(L + (E, D, F), pd + ("experts", "embed", "mlp")),
            w3=ParamDef(L + (E, D, F), pd + ("experts", "embed", "mlp")),
            w2=ParamDef(L + (E, F, D), pd + ("experts", "mlp", "embed")),
        )
    else:
        assert n_layers is None
        out.update(
            w1=[ParamDef((D, F), ("embed", "mlp")) for _ in range(E)],
            w3=[ParamDef((D, F), ("embed", "mlp")) for _ in range(E)],
            w2=[ParamDef((F, D), ("mlp", "embed")) for _ in range(E)],
        )
    return out


def _route(x_flat, gate_w, cfg):
    """x_flat: (G, Tg, D) -> (expert ids (G,Tg,k), combine gates, aux loss)."""
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    logits = jnp.einsum("gtd,de->gte", x_flat, gate_w,
                        preferred_element_type=jnp.float32)
    gates_all = jax.nn.softmax(logits, axis=-1)
    top_g, top_e = jax.lax.top_k(gates_all, k)                  # (G,Tg,k)
    top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance loss
    me = jnp.mean(gates_all, axis=(0, 1))                        # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=2), axis=(0, 1))
    aux = E * jnp.sum(me * ce)
    return top_e, top_g, aux


def _dispatch_buffers(top_e, top_g, Tg: int, E: int, C: int):
    """Sorted-scatter dispatch: per expert, up to C token slots per group.

    Returns (buf_tok (G,E,C) int32 indices into Tg [Tg == dropped],
             buf_gate (G,E,C) f32).
    """
    G, T, k = top_e.shape
    flat_e = top_e.reshape(G, T * k)
    flat_t = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[:, None], (T, k))
    flat_t = jnp.broadcast_to(flat_t.reshape(1, T * k), (G, T * k))
    flat_g = top_g.reshape(G, T * k)

    order = jnp.argsort(flat_e, axis=-1, stable=True)
    se = jnp.take_along_axis(flat_e, order, axis=-1)
    st = jnp.take_along_axis(flat_t, order, axis=-1)
    sg = jnp.take_along_axis(flat_g, order, axis=-1)

    # position within expert segment
    starts = jax.vmap(lambda row: jnp.searchsorted(row, jnp.arange(E), side="left"))(se)
    pos = jnp.arange(T * k, dtype=jnp.int32)[None, :] - jnp.take_along_axis(
        starts, se, axis=-1).astype(jnp.int32)
    keep = pos < C
    dest = jnp.where(keep, se * C + pos, E * C)                 # E*C == drop slot

    buf_tok = jnp.full((G, E * C + 1), Tg, dtype=jnp.int32)
    buf_gate = jnp.zeros((G, E * C + 1), jnp.float32)
    buf_tok = jax.vmap(lambda b, d, t: b.at[d].set(t, mode="drop"))(buf_tok, dest, st)
    buf_gate = jax.vmap(lambda b, d, g: b.at[d].set(g, mode="drop"))(buf_gate, dest, sg)
    return (buf_tok[:, : E * C].reshape(G, E, C),
            buf_gate[:, : E * C].reshape(G, E, C))


def moe_ffn_tp(w, x, cfg):
    """TP/scan-over-experts path.  x: (B, S, D) -> (out, aux_loss)."""
    B, S, D = x.shape
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    Gr = min(cfg.moe.n_groups, B * S)
    T = B * S
    assert T % Gr == 0, (T, Gr)
    Tg = T // Gr
    C = max(1, int(Tg * k * cfg.moe.capacity_factor / E))

    xf = x.reshape(Gr, Tg, D)
    top_e, top_g, aux = _route(xf, w["gate"], cfg)
    buf_tok, buf_gate = _dispatch_buffers(top_e, top_g, Tg, E, C)

    # pad a zero row per group so dropped slots (index Tg) gather zeros
    xpad = jnp.concatenate([xf, jnp.zeros((Gr, 1, D), xf.dtype)], axis=1)

    def expert_step(acc, ew):
        w1, w3, w2, tok, gate = ew                     # (D,F),(D,F),(F,D),(G,C),(G,C)
        xg = jnp.take_along_axis(xpad, tok[..., None], axis=1)   # (G,C,D)
        h = jax.nn.silu(jnp.einsum("gcd,df->gcf", xg, w1))
        h = h * jnp.einsum("gcd,df->gcf", xg, w3)
        o = jnp.einsum("gcf,fd->gcd", h, w2)
        o = o * gate[..., None].astype(o.dtype)
        acc = jax.vmap(lambda a, t, v: a.at[t].add(v, mode="drop"))(acc, tok, o)
        return acc, None

    acc0 = jnp.zeros((Gr, Tg + 1, D), x.dtype)
    tok_e = jnp.swapaxes(buf_tok, 0, 1)
    gate_e = jnp.swapaxes(buf_gate, 0, 1)
    if getattr(cfg, "scan_layers", True):
        xs = (w["w1"], w["w3"], w["w2"], tok_e, gate_e)
        # remat: without it, scan-over-experts saves every expert's gathered
        # token block for the backward pass (E × (G,C,D) ≈ tens of GiB at
        # train_4k scale); the accumulator carry itself is linear and needs
        # no saving.
        acc, _ = jax.lax.scan(jax.remat(expert_step), acc0, xs)
    else:  # unrolled for the dry-run cost probe (list- or stacked weights)
        acc = acc0
        for e in range(E):
            ew = (w["w1"][e], w["w3"][e], w["w2"][e], tok_e[e], gate_e[e])
            acc, _ = expert_step(acc, ew)
    return acc[:, :Tg].reshape(B, S, D), aux


def moe_ffn_ep(w, x, cfg, mesh):
    """Expert-parallel path via shard_map all-to-all over the 'model' axis."""
    if isinstance(w.get("w1"), (list, tuple)):  # probe (list-form) weights
        w = dict(w, w1=jnp.stack(w["w1"]), w3=jnp.stack(w["w3"]),
                 w2=jnp.stack(w["w2"]))
    B, S, D = x.shape
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    tp = mesh.shape["model"]
    assert E % tp == 0, (E, tp)
    E_local = E // tp

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    batch_ax = dp_axes if B % _axes_size(mesh, dp_axes) == 0 else ()

    def local_moe(xl, gate_w, w1, w3, w2):
        # xl: (Bl, S, D) replicated over 'model'; sub-slice tokens over model
        Bl = xl.shape[0]
        Tl = Bl * S
        xt = xl.reshape(Tl, D)
        midx = jax.lax.axis_index("model")
        Tm = Tl // tp
        xt = jax.lax.dynamic_slice_in_dim(xt, midx * Tm, Tm, axis=0)  # (Tm, D)

        logits = jnp.einsum("td,de->te", xt, gate_w,
                            preferred_element_type=jnp.float32)
        gates_all = jax.nn.softmax(logits, -1)
        top_g, top_e = jax.lax.top_k(gates_all, k)
        top_g = top_g / jnp.maximum(top_g.sum(-1, keepdims=True), 1e-9)

        C = max(1, int(Tm * k * cfg.moe.capacity_factor / E))
        buf_tok, buf_gate = _dispatch_buffers(
            top_e[None], top_g[None], Tm, E, C)          # (1,E,C)
        buf_tok, buf_gate = buf_tok[0], buf_gate[0]
        xpad = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)], axis=0)
        xsend = xpad[buf_tok]                            # (E, C, D)

        # exchange: every rank sends its C-slot block for the experts each
        # peer owns; receives (tp, E_local, C, D) -> tokens for MY experts
        xsend = xsend.reshape(tp, E_local, C, D)
        xrecv = jax.lax.all_to_all(xsend, "model", split_axis=0, concat_axis=0,
                                   tiled=False)          # (tp, E_local, C, D)
        xr = jnp.swapaxes(xrecv, 0, 1)                   # (E_local, tp, C, D)
        xr = xr.reshape(E_local, tp * C, D)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xr, w1))
        h = h * jnp.einsum("ecd,edf->ecf", xr, w3)
        o = jnp.einsum("ecf,efd->ecd", h, w2)            # (E_local, tp*C, D)

        o = o.reshape(E_local, tp, C, D).swapaxes(0, 1)  # (tp, E_local, C, D)
        oback = jax.lax.all_to_all(o, "model", split_axis=0, concat_axis=0,
                                   tiled=False)          # (tp, E_local, C, D)
        oback = oback.reshape(E, C, D) * buf_gate[..., None].astype(o.dtype)

        out = jnp.zeros((Tm + 1, D), xl.dtype)
        out = out.at[buf_tok.reshape(-1)].add(
            oback.reshape(-1, D).astype(xl.dtype), mode="drop")[:Tm]
        # reassemble the full token set across model ranks
        out = jax.lax.all_gather(out, "model", axis=0, tiled=True)  # (Tl, D)
        return out.reshape(Bl, S, D)

    in_specs = (P(batch_ax if batch_ax else None, None, None),
                P(None, None),
                P("model", None, None), P("model", None, None),
                P("model", None, None))
    out_specs = P(batch_ax if batch_ax else None, None, None)
    fn = jax.shard_map(local_moe, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out = fn(x, w["gate"], w["w1"], w["w3"], w["w2"])
    # aux loss computed (cheaply, replicated) outside the shard_map
    _, _, aux = _route(x.reshape(1, B * S, D), w["gate"], cfg)
    return out, aux


def _axes_size(mesh, axes):
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def moe_ffn(w, x, cfg, mesh=None):
    if cfg.moe_path == "ep" and mesh is not None:
        B, S, _ = x.shape
        dp = _axes_size(mesh, tuple(a for a in ("pod", "data")
                                    if a in mesh.shape))
        tp = mesh.shape.get("model", 1)
        # EP needs ≥1 token per (data, model) rank pair; small decode
        # batches fall back to the TP path
        if (B * S) % (dp * tp) == 0 and B % dp == 0:
            return moe_ffn_ep(w, x, cfg, mesh)
    return moe_ffn_tp(w, x, cfg)
