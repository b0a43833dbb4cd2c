"""Mixture-of-Experts FFN with top-k routing and expert parallelism.

Two dispatch implementations:

  * ``sort`` (default, production) — Megablocks/MaxText-style: flatten the
    (token, k) assignments, stable-sort by expert id, compute each
    assignment's position inside its expert via searchsorted, scatter into a
    capacity-bounded (E, C, D) buffer, run the expert matmuls as one batched
    einsum, gather + weighted-combine back.  Gathers/scatters are memory ops
    — HLO FLOPs stay ≈ active FLOPs (top-k × tokens × expert size × cf),
    which keeps the 6·N_active·D roofline honest.
  * ``dense`` (ablation / small configs) — GShard-style one-hot dispatch and
    combine einsums.  Simple and collective-friendly but pays O(N·E·C·D)
    dispatch FLOPs and memory; used in tests and for the §Perf comparison.

Expert parallelism: the (E, ...) expert dims carry the "experts" logical
axis, sharded over the "model" mesh axis; XLA GSPMD inserts the all-to-all
for the sharded scatter/gather.  Shared experts (DeepSeekMoE) are a fused
dense MLP of width num_shared × d_ff, always active.

Load balancing uses the Switch-Transformer auxiliary loss
(E · Σ_e fraction_e · prob_e) plus a router z-loss.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.param import ParamBuilder, fan_in_init, normal_init


class MoEDims(NamedTuple):
    d_model: int
    d_ff: int
    num_experts: int
    top_k: int
    num_shared: int
    capacity_factor: float


def init_moe(b: ParamBuilder, name: str, dims: MoEDims) -> None:
    d, f, E = dims.d_model, dims.d_ff, dims.num_experts
    with b.scope(name):
        b.param("router", (d, E), ("embed", "experts"), normal_init(0.02),
                dtype=jnp.float32)
        b.param("w_gate", (E, d, f), ("experts", "embed", "expert_mlp"), fan_in_init())
        b.param("w_up", (E, d, f), ("experts", "embed", "expert_mlp"), fan_in_init())
        b.param("w_down", (E, f, d), ("experts", "expert_mlp", "embed"), fan_in_init())
        if dims.num_shared:
            layers.init_mlp(b, "shared", d, dims.num_shared * f)


def capacity(num_tokens: int, dims: MoEDims, *, round_multiple: int = 8) -> int:
    c = math.ceil(num_tokens * dims.top_k * dims.capacity_factor / dims.num_experts)
    # MXU-friendly: round up to a multiple of 8, at least top_k.  Per-sequence
    # dispatch (small num_tokens, vmapped over B) passes round_multiple=1:
    # rounding a ~1-slot capacity up to 8 for every sequence in the batch
    # inflates the expert buffers and padded-slot FFN work ~8x.
    return max(dims.top_k, -(-c // round_multiple) * round_multiple)


def _routing(params, x_flat: jax.Array, dims: MoEDims):
    """Router probabilities and top-k assignment.  x_flat: (N, D) -> ..."""
    logits = (x_flat.astype(jnp.float32) @ params["router"])  # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, dims.top_k)  # (N, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)  # renorm
    # Switch aux loss: mean fraction routed (top-1 assignments) x mean prob
    E = dims.num_experts
    frac = jnp.mean(jax.nn.one_hot(top_e, E, dtype=jnp.float32), axis=(0, 1))
    mean_p = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac * mean_p)
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return top_p, top_e, aux, zloss


def _expert_ffn(params, xs: jax.Array) -> jax.Array:
    """xs: (E, C, D) -> (E, C, D) through each expert's SwiGLU."""
    dt = xs.dtype
    gate = jnp.einsum("ecd,edf->ecf", xs, params["w_gate"].astype(dt))
    up = jnp.einsum("ecd,edf->ecf", xs, params["w_up"].astype(dt))
    hidden = jax.nn.silu(gate.astype(jnp.float32)).astype(dt) * up
    return jnp.einsum("ecf,efd->ecd", hidden, params["w_down"].astype(dt))


def _sort_dispatch(params, x_flat: jax.Array, dims: MoEDims,
                   cap_round: int = 8):
    N, D = x_flat.shape
    E, k = dims.num_experts, dims.top_k
    C = capacity(N, dims, round_multiple=cap_round)
    top_p, top_e, aux, zloss = _routing(params, x_flat, dims)

    flat_e = top_e.reshape(-1)  # (N*k,)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))  # first slot per expert
    pos = jnp.arange(N * k) - starts[sorted_e]  # slot within expert
    token_of = order // k  # source token per sorted slot
    keep = pos < C

    # scatter tokens into the (E, C, D) expert buffer; dropped slots vanish
    buf = jnp.zeros((E, C, D), x_flat.dtype)
    buf = buf.at[sorted_e, jnp.where(keep, pos, C)].set(
        x_flat[token_of], mode="drop"
    )
    out_buf = _expert_ffn(params, buf)  # (E, C, D)

    # gather back: invert the sort to (N, k) slots
    inv = jnp.argsort(order)  # (N*k,) sorted-slot index of assignment i
    slot_e = sorted_e[inv].reshape(N, k)
    slot_pos = pos[inv].reshape(N, k)
    slot_keep = keep[inv].reshape(N, k)
    gathered = out_buf[slot_e, jnp.clip(slot_pos, 0, C - 1)]  # (N, k, D)
    w = (top_p * slot_keep).astype(gathered.dtype)
    return jnp.einsum("nkd,nk->nd", gathered, w), aux, zloss


def _dense_dispatch(params, x_flat: jax.Array, dims: MoEDims,
                    cap_round: int = 8):
    """GShard-style einsum dispatch (ablation path)."""
    N, D = x_flat.shape
    E, k = dims.num_experts, dims.top_k
    C = capacity(N, dims, round_multiple=cap_round)
    top_p, top_e, aux, zloss = _routing(params, x_flat, dims)
    # position of each assignment inside its expert via cumsum of one-hots
    onehot = jax.nn.one_hot(top_e, E, dtype=jnp.int32)  # (N, k, E)
    flat = onehot.reshape(N * k, E)
    pos = jnp.cumsum(flat, axis=0) - flat  # (N*k, E) slots before this one
    pos = (pos * flat).sum(-1).reshape(N, k)  # (N, k)
    keep = pos < C
    # dispatch: (N, k, E, C) one-hot
    disp = (
        jax.nn.one_hot(top_e, E, dtype=x_flat.dtype)[..., None]
        * jax.nn.one_hot(jnp.where(keep, pos, C), C + 1, dtype=x_flat.dtype)[
            :, :, None, :C
        ]
    )  # (N, k, E, C)
    buf = jnp.einsum("nkec,nd->ecd", disp, x_flat)
    out_buf = _expert_ffn(params, buf)
    combine = disp * top_p[..., None, None].astype(x_flat.dtype)
    out = jnp.einsum("nkec,ecd->nd", combine, out_buf)
    return out, aux, zloss


def _local_pack(params, x_loc: jax.Array, dims: MoEDims, cap: int):
    """Route local tokens and pack them into a capacity buffer (E, C, D).

    Runs per-device inside shard_map; the scatter is device-local, so the
    only cross-device traffic in the a2a impl is the two all_to_alls.
    """
    N, D = x_loc.shape
    E, k = dims.num_experts, dims.top_k
    top_p, top_e, aux, zloss = _routing(params, x_loc, dims)
    flat_e = top_e.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E))
    pos = jnp.arange(N * k) - starts[sorted_e]
    token_of = order // k
    keep = pos < cap
    buf = jnp.zeros((E, cap, D), x_loc.dtype)
    buf = buf.at[sorted_e, jnp.where(keep, pos, cap)].set(
        x_loc[token_of], mode="drop"
    )
    meta = (order, sorted_e, pos, keep, top_p)
    return buf, meta, aux, zloss


def _local_combine(out_buf: jax.Array, meta, N: int, k: int, cap: int):
    order, sorted_e, pos, keep, top_p = meta
    inv = jnp.argsort(order)
    slot_e = sorted_e[inv].reshape(N, k)
    slot_pos = pos[inv].reshape(N, k)
    slot_keep = keep[inv].reshape(N, k)
    gathered = out_buf[slot_e, jnp.clip(slot_pos, 0, cap - 1)]
    w = (top_p * slot_keep).astype(gathered.dtype)
    return jnp.einsum("nkd,nk->nd", gathered, w)


def moe_ffn_a2a(
    params, x_flat: jax.Array, dims: MoEDims, mesh, model_axis: str = "model"
):
    """Expert-parallel MoE via explicit shard_map + all_to_all.

    Tokens stay sharded over the data axes; experts are sharded over the
    model axis.  Each device packs its local tokens into an (E, C_loc, D)
    capacity buffer, all_to_all sends each expert's slice to the device
    that owns it, local experts run one batched einsum, and the reverse
    all_to_all returns results for a local weighted combine.  Collective
    bytes = 2 x top_k x capacity_factor x token bytes — the GSPMD
    scatter/gather path this replaces all-gathered the full activation per
    layer (see EXPERIMENTS.md §Perf).
    """
    from jax.sharding import PartitionSpec as P

    E = dims.num_experts
    Pm = mesh.shape[model_axis]
    assert E % Pm == 0, (E, Pm)
    E_loc = E // Pm
    data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    N_glob, D = x_flat.shape
    n_loc = N_glob // n_data
    cap = capacity(n_loc, dims)

    def local_fn(x_loc, router, wg, wu, wd):
        buf, meta, aux, zloss = _local_pack(
            {"router": router}, x_loc, dims, cap
        )
        # (E, C, D) -> (Pm, E_loc, C, D); tiled all_to_all over the model
        # axis with split==concat axis exchanges the Pm blocks between
        # devices (a device-transpose): afterwards dim 0 indexes the SOURCE
        # device whose tokens our local experts must process.
        buf = buf.reshape(Pm, E_loc, cap, D)
        buf = jax.lax.all_to_all(buf, model_axis, 0, 0, tiled=True)
        xs = buf.transpose(1, 0, 2, 3).reshape(E_loc, Pm * cap, D)
        out = _expert_ffn({"w_gate": wg, "w_up": wu, "w_down": wd}, xs)
        out = out.reshape(E_loc, Pm, cap, D).transpose(1, 0, 2, 3)
        out = jax.lax.all_to_all(out, model_axis, 0, 0, tiled=True)
        out_buf = out.reshape(E, cap, D)
        y = _local_combine(out_buf, meta, x_loc.shape[0], dims.top_k, cap)
        # average aux terms over every mesh axis so the output is replicated
        aux = jax.lax.pmean(aux, data_axes + (model_axis,))
        zloss = jax.lax.pmean(zloss, data_axes + (model_axis,))
        return y, aux, zloss

    first = data_axes if data_axes else None
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(
            P(first, None),  # tokens: data-sharded
            P(),  # router replicated
            P(model_axis), P(model_axis), P(model_axis),  # expert shards
        ),
        out_specs=(P(first, None), P(), P()),
        check_vma=False,
    )
    y, aux, zloss = fn(
        x_flat, params["router"], params["w_gate"], params["w_up"],
        params["w_down"],
    )
    return y.reshape(N_glob, D), aux, zloss


def moe_ffn(
    params, x: jax.Array, dims: MoEDims, impl: str = "sort", mesh=None
) -> tuple[jax.Array, jax.Array]:
    """x: (B, T, D) -> (out (B, T, D), aux_loss scalar).

    The ``sort``/``dense`` paths dispatch **per sequence** (vmap over B):
    capacity slots are assigned by cumulative position, so contending for
    them across the flattened B*T stream would let one sequence's suffix
    evict another sequence's prefix from an expert — breaking the
    autoregressive causality invariant (test_causality.py).  Per-row
    dispatch keeps slot assignment causal within each sequence and
    independent across them.

    The ``a2a`` path still routes the flattened B*T stream (per-sequence
    dispatch inside its shard_map would change the all_to_all payload
    shapes): with a tight ``capacity_factor`` its drops can differ from
    ``sort``/``dense`` — cross-sequence slot contention within a data
    shard.  Equivalence to ``sort`` holds at generous capacity (the regime
    test_perf_features.py checks); don't mix impls at small capacity
    factors.
    """
    B, T, D = x.shape
    x_flat = x.reshape(B * T, D)
    if impl == "a2a":
        if mesh is None:
            raise ValueError("moe impl 'a2a' needs a mesh")
        out, aux, zloss = moe_ffn_a2a(params, x_flat, dims, mesh)
    elif impl in ("sort", "dense"):
        fn = _sort_dispatch if impl == "sort" else _dense_dispatch
        out, aux, zloss = jax.vmap(
            lambda xr: fn(params, xr, dims, cap_round=1)
        )(x)
        out = out.reshape(B * T, D)
        aux = jnp.mean(aux)
        zloss = jnp.mean(zloss)
    else:
        raise ValueError(f"unknown moe impl {impl!r}")
    if dims.num_shared:
        out = out + layers.mlp(params["shared"], x).reshape(B * T, D)
    return out.reshape(B, T, D), aux + 1e-3 * zloss
