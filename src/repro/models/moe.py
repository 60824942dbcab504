"""Mixture-of-Experts: top-k routing + sort-based capacity dispatch (EP).

Dispatch is the permutation formulation (argsort by expert id → gather
into an (E, C, D) buffer → grouped einsum → scatter back), not the
one-hot (T, E, C) einsum — with E=256 the one-hot dispatch tensor alone
would dwarf the activations. Experts shard over the "model" mesh axis
(expert parallelism); the token→expert gather/scatter lowers to
all-to-alls under pjit, which the roofline's collective term prices.

Routing faithfully covers the assigned archs:
  * plain softmax top-k                      (jamba 16e top-2)
  * group-limited top-k + shared experts     (deepseek-v2: 160e top-6 + 2 shared)
  * sigmoid scoring w/ normalized weights    (deepseek-v3: 256e top-8 + 1 shared)

Tokens beyond an expert's capacity are dropped (output 0 for that slot) —
the classic Switch/GShard behaviour; capacity_factor controls slack.

The paper-technique tie-in (DESIGN.md §Arch-applicability): static even
capacity per expert is the same *even-tiling invariant* the paper gets
from work stealing — load balance enforced by construction, measured by
the aux loss.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from repro.configs.base import ModelConfig
from repro.models.common import ParamSpec
from repro.models.hints import get_hint
from repro.models.layers import _act


def moe_schema(cfg: ModelConfig) -> dict:
    ff = cfg.moe_d_ff or cfg.d_ff
    e = cfg.n_experts
    d = {
        "router": ParamSpec((cfg.d_model, e), ("embed", None), "small"),
        "up": ParamSpec((e, cfg.d_model, ff), ("experts", "embed", "ff")),
        "gate": ParamSpec((e, cfg.d_model, ff), ("experts", "embed", "ff")),
        "down": ParamSpec((e, ff, cfg.d_model), ("experts", "ff", "embed")),
    }
    if cfg.n_shared_experts:
        sff = ff * cfg.n_shared_experts
        d["shared_up"] = ParamSpec((cfg.d_model, sff), ("embed", "ff"))
        d["shared_gate"] = ParamSpec((cfg.d_model, sff), ("embed", "ff"))
        d["shared_down"] = ParamSpec((sff, cfg.d_model), ("ff", "embed"))
    return d


def _route(p: dict, x_flat: jax.Array, cfg: ModelConfig):
    """x_flat: (T, D) → (weights (T,k), expert_idx (T,k), aux_loss)."""
    logits = jnp.einsum(
        "td,de->te", x_flat.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    e, k = cfg.n_experts, cfg.top_k
    if cfg.router_scale:  # deepseek-v3 style sigmoid affinity
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)

    if cfg.n_groups > 1:  # group-limited routing (deepseek)
        g = cfg.n_groups
        sg = scores.reshape(-1, g, e // g)
        # group affinity = sum of its top-2 expert scores
        top2 = jax.lax.top_k(sg, min(2, e // g))[0].sum(-1)  # (T, g)
        _, gidx = jax.lax.top_k(top2, cfg.topk_groups)  # (T, topk_groups)
        gmask = jnp.zeros_like(top2).at[
            jnp.arange(top2.shape[0])[:, None], gidx
        ].set(1.0)
        scores = (sg * gmask[..., None]).reshape(-1, e)

    weights, idx = jax.lax.top_k(scores, k)  # (T, k)
    if cfg.router_scale:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)

    # load-balance aux loss (GShard): E * Σ_e f_e · p_e
    probs = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(idx[:, 0], e)  # primary assignment
    f = onehot.mean(0)
    pbar = probs.mean(0)
    aux = e * jnp.sum(f * pbar)
    return weights.astype(x_flat.dtype), idx, aux


# Below this token count the dispatch is exact (cap = T: nothing can ever
# drop) — decode batches and short prefills are always served dropless,
# matching production MoE inference. Above it, capacity_factor governs.
_DROPLESS_MAX_TOKENS = 4096


def moe_ffn(p: dict, x: jax.Array, cfg: ModelConfig, capacity_factor: float = 1.25):
    """x: (B, S, D) → (out (B, S, D), aux_loss scalar).

    If the launcher installed an expert-parallel hint (``ep_axis`` +
    ``mesh``), dispatch runs shard-local inside ``shard_map`` with a
    single psum combine — see ``_moe_ffn_ep``. Otherwise the global
    (auto-sharded) formulation below is used.
    """
    if get_hint("ep_axis") is not None and get_hint("mesh") is not None:
        return _moe_ffn_ep(p, x, cfg, capacity_factor)
    return _moe_ffn_global(p, x, cfg, capacity_factor)


def _moe_ffn_global(
    p: dict, x: jax.Array, cfg: ModelConfig, capacity_factor: float = 1.25
):
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xf = x.reshape(t, d)
    weights, idx, aux = _route(p, xf, cfg)

    if t <= _DROPLESS_MAX_TOKENS:
        cap = t  # exact: top-k indices are unique per token
    else:
        cap = min(t, int(max(1, round(k * t * capacity_factor / e))))

    # ---- permutation dispatch ------------------------------------------
    flat_expert = idx.reshape(-1)  # (T·k,)
    flat_token = jnp.repeat(jnp.arange(t), k)  # (T·k,)
    flat_w = weights.reshape(-1)
    order = jnp.argsort(flat_expert)  # stable
    se, stok, sw = flat_expert[order], flat_token[order], flat_w[order]
    # rank within expert = position − start of that expert's run
    pos = jnp.arange(t * k)
    seg_start = jnp.searchsorted(se, jnp.arange(e), side="left")  # (E,)
    rank = pos - seg_start[se]
    keep = rank < cap
    slot = se * cap + jnp.where(keep, rank, 0)  # flattened (E·C) slot

    buf = jnp.zeros((e * cap, d), x.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], xf[stok], 0))
    buf = buf.reshape(e, cap, d)

    # ---- grouped expert FFN (shards over "model" via the experts axis) --
    up = jnp.einsum("ecd,edf->ecf", buf, p["up"])
    gate = jnp.einsum("ecd,edf->ecf", buf, p["gate"])
    h = _act(gate, cfg.act) * up
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["down"]).reshape(e * cap, d)

    # ---- combine: scatter back, weighted -------------------------------
    contrib = out_buf[slot] * (sw * keep)[:, None]
    out = jnp.zeros((t, d), x.dtype).at[stok].add(contrib)

    if cfg.n_shared_experts:
        su = jnp.einsum("td,df->tf", xf, p["shared_up"])
        sg = jnp.einsum("td,df->tf", xf, p["shared_gate"])
        out = out + jnp.einsum("tf,fd->td", _act(sg, cfg.act) * su, p["shared_down"])

    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
def _dispatch_and_compute(xf, weights, idx, up, gate, down, cfg, cap, e_base, e_loc):
    """Shard-local capacity dispatch for experts [e_base, e_base+e_loc)."""
    t, d = xf.shape
    k = cfg.top_k
    flat_expert = idx.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(t), k)
    flat_w = weights.reshape(-1)
    local = (flat_expert >= e_base) & (flat_expert < e_base + e_loc)
    le = jnp.where(local, flat_expert - e_base, e_loc)  # e_loc = overflow bin
    order = jnp.argsort(le)
    se, stok, sw, sl = le[order], flat_token[order], flat_w[order], local[order]
    pos = jnp.arange(se.shape[0])
    seg_start = jnp.searchsorted(se, jnp.arange(e_loc), side="left")
    safe_se = jnp.minimum(se, e_loc - 1)
    rank = pos - seg_start[safe_se]
    keep = sl & (rank < cap)
    slot = jnp.where(keep, safe_se * cap + rank, 0)

    buf = jnp.zeros((e_loc * cap, d), xf.dtype)
    buf = buf.at[slot].add(jnp.where(keep[:, None], xf[stok], 0))
    buf = buf.reshape(e_loc, cap, d)
    h = _act(jnp.einsum("ecd,edf->ecf", buf, gate), cfg.act) * jnp.einsum(
        "ecd,edf->ecf", buf, up
    )
    out_buf = jnp.einsum("ecf,efd->ecd", h, down).reshape(e_loc * cap, d)
    contrib = out_buf[slot] * (sw * keep)[:, None]
    return jnp.zeros((t, d), xf.dtype).at[stok].add(contrib)


def _a2a_body(xl, w, cfg, mesh, ep_axis, capacity_factor):
    """GShard-style token-parallel dispatch (hint moe_impl="a2a").

    Tokens arrive replicated along the EP axis; each shard routes its
    1/n_shards slice, all-to-alls token payloads to their expert owners,
    computes, all-to-alls back, and the combined slices are re-gathered.
    Wire per layer ≈ 2·(k/n)·T·D a2a + T·D/n AG — several× less than the
    psum-combine variant whose backward pays f32 (T, D) all-reduces.
    """
    d = xl.shape[-1]
    e, k = cfg.n_experts, cfg.top_k
    n = dict(mesh.shape)[ep_axis]
    e_loc = e // n
    bl, sl_, _ = xl.shape
    t = bl * sl_
    tl = t // n
    shard = jax.lax.axis_index(ep_axis)
    xf = xl.reshape(t, d)
    xs = jax.lax.dynamic_slice_in_dim(xf, shard * tl, tl, axis=0)  # my slice

    weights, idx, aux = _route(w, xs, cfg)  # (tl, k)
    if tl * k <= _DROPLESS_MAX_TOKENS:
        cap_s = tl * k  # dropless at decode/small-prefill scales
    else:
        cap_s = min(tl * k, int(max(1, round(k * tl * capacity_factor / n))))

    # ---- build send buffers keyed by destination shard -------------------
    flat_e = idx.reshape(-1)  # (tl·k,)
    flat_tok = jnp.repeat(jnp.arange(tl), k)
    flat_w = weights.reshape(-1)
    dest = flat_e // e_loc
    order = jnp.argsort(dest)
    sd, se, stok, sw = dest[order], flat_e[order], flat_tok[order], flat_w[order]
    pos = jnp.arange(tl * k)
    seg = jnp.searchsorted(sd, jnp.arange(n), side="left")
    rank = pos - seg[sd]
    keep = rank < cap_s
    slot = jnp.where(keep, sd * cap_s + rank, 0)

    payload = jnp.zeros((n * cap_s, d), xf.dtype).at[slot].add(
        jnp.where(keep[:, None], xs[stok], 0)
    )
    # metadata rides in int/float lanes (−1 = empty slot)
    meta_le = jnp.full((n * cap_s,), -1, jnp.int32).at[slot].set(
        jnp.where(keep, (se % e_loc).astype(jnp.int32), -1)
    )
    meta_tok = jnp.zeros((n * cap_s,), jnp.int32).at[slot].set(
        jnp.where(keep, stok.astype(jnp.int32), 0)
    )
    meta_w = jnp.zeros((n * cap_s,), jnp.float32).at[slot].set(
        jnp.where(keep, sw.astype(jnp.float32), 0.0)
    )

    def a2a(z):
        return jax.lax.all_to_all(
            z.reshape((n, cap_s) + z.shape[1:]), ep_axis, 0, 0, tiled=False
        ).reshape((n * cap_s,) + z.shape[1:])

    r_pay = a2a(payload)  # tokens for MY experts, grouped by source shard
    r_le = a2a(meta_le)
    r_w = a2a(meta_w)

    # ---- local expert compute (second, local dispatch by expert id) ------
    cap2 = n * cap_s  # worst case: every received row hits one expert
    valid = r_le >= 0
    le = jnp.where(valid, r_le, e_loc)
    order2 = jnp.argsort(le)
    le2, src2 = le[order2], jnp.arange(n * cap_s)[order2]
    seg2 = jnp.searchsorted(le2, jnp.arange(e_loc), side="left")
    pos2 = jnp.arange(n * cap_s)
    safe_le2 = jnp.minimum(le2, e_loc - 1)
    rank2 = pos2 - seg2[safe_le2]
    keep2 = (le2 < e_loc) & (rank2 < cap2)
    slot2 = jnp.where(keep2, safe_le2 * cap2 + rank2, 0)
    buf = jnp.zeros((e_loc * cap2, d), xf.dtype).at[slot2].add(
        jnp.where(keep2[:, None], r_pay[src2], 0)
    )
    buf = buf.reshape(e_loc, cap2, d)
    h = _act(jnp.einsum("ecd,edf->ecf", buf, w["gate"]), cfg.act) * jnp.einsum(
        "ecd,edf->ecf", buf, w["up"]
    )
    out_buf = jnp.einsum("ecf,efd->ecd", h, w["down"]).reshape(e_loc * cap2, d)
    # un-permute back to received-row order
    back = jnp.zeros((n * cap_s, d), xf.dtype).at[src2].add(
        jnp.where(keep2[:, None], out_buf[slot2], 0)
    )

    s_pay = a2a(back)  # results return to token owners
    contrib = s_pay * (meta_w * (meta_le >= 0))[:, None].astype(s_pay.dtype)
    out_s = jnp.zeros((tl, d), xf.dtype).at[meta_tok].add(contrib)

    if cfg.n_shared_experts:
        su = jnp.einsum("td,df->tf", xs, w["shared_up"])
        sg = jnp.einsum("td,df->tf", xs, w["shared_gate"])
        out_s = out_s + jnp.einsum(
            "tf,fd->td", _act(sg, cfg.act) * su, w["shared_down"]
        )

    out = jax.lax.all_gather(out_s, ep_axis, axis=0, tiled=True)  # (t, d)
    return out.reshape(bl, sl_, d), aux


def _moe_ffn_ep(p: dict, x: jax.Array, cfg: ModelConfig, capacity_factor: float):
    """Expert-parallel MoE: shard-local dispatch + one psum combine.

    Tokens are replicated along the EP ("model") axis under the TP
    layout, so no token all-to-all is needed at all: each shard gathers
    the tokens routed to ITS experts locally, runs them, and the partial
    outputs are summed across the axis — one (T_loc, D) all-reduce per
    MoE layer instead of an all-reduce of the full (E·C, D) dispatch
    buffer (≈80× less wire for deepseek-v3). With hint moe_impl="a2a"
    the GShard token-parallel dispatch (``_a2a_body``) is used instead.
    """
    mesh = get_hint("mesh")
    ep_axis = get_hint("ep_axis")
    dp_axes = get_hint("batch")
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    n_shards = dict(mesh.shape)[ep_axis]
    e_loc = e // n_shards
    x_spec = P(dp_axes, None, None)
    w_specs = {
        "router": P(None, None),
        "up": P(ep_axis, None, None),
        "gate": P(ep_axis, None, None),
        "down": P(ep_axis, None, None),
    }
    for extra in ("shared_up", "shared_gate", "shared_down"):
        if extra in p:
            w_specs[extra] = P(None, None)
    wp = {kk: p[kk] for kk in w_specs}

    use_a2a = get_hint("moe_impl") == "a2a"

    def _dp_mean(aux):
        if dp_axes:
            import math as _math

            n_dp = _math.prod(dict(mesh.shape)[a] for a in dp_axes)
            return jax.lax.psum(aux, dp_axes) / n_dp
        return aux

    def body(xl, w):
        bl, sl_, _ = xl.shape
        t = bl * sl_
        if use_a2a and t % n_shards == 0 and (t // n_shards) >= 1:
            out, aux = _a2a_body(xl, w, cfg, mesh, ep_axis, capacity_factor)
            # aux differs per token slice → mean over EP too
            aux = jax.lax.psum(aux, ep_axis) / n_shards
            return out, _dp_mean(aux)
        xf = xl.reshape(t, d)
        weights, idx, aux = _route(w, xf, cfg)
        if t <= _DROPLESS_MAX_TOKENS:
            cap = t
        else:
            cap = min(t, int(max(1, round(k * t * capacity_factor / e))))
        shard = jax.lax.axis_index(ep_axis)
        e_base = shard * e_loc
        out = _dispatch_and_compute(
            xf, weights, idx, w["up"], w["gate"], w["down"], cfg, cap, e_base, e_loc
        )
        # combine in bf16: the psum is the EP wire hot-spot; an f32 psum
        # (XLA hoisting the downstream norm's convert) doubles it.
        out = jax.lax.psum(out.astype(jnp.bfloat16), ep_axis).astype(xf.dtype)
        if cfg.n_shared_experts:
            su = jnp.einsum("td,df->tf", xf, w["shared_up"])
            sg = jnp.einsum("td,df->tf", xf, w["shared_gate"])
            out = out + jnp.einsum(
                "tf,fd->td", _act(sg, cfg.act) * su, w["shared_down"]
            )
        # aux is identical along the EP axis (tokens replicated there) but
        # differs per data shard — mean over DP makes it truly replicated.
        return out.reshape(bl, sl_, d), _dp_mean(aux)

    out, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(x_spec, w_specs),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, wp)
    return out, aux
