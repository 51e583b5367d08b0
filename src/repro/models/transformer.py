"""Decoder/encoder transformer blocks: GQA attention, (gated) MLP, and a
shard_map expert-parallel MoE layer.

Layer stacks are scanned; interleaved stacks (e.g. llama4's dense/MoE
alternation) scan over *periods* of ``moe_every`` layers.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..dist.sharding import axis_size, kv_head_axis, shard_map_compat
from ..kernels import ops as K
from .common import (ModelConfig, Params, act_fn, apply_rope, decode_attention,
                     dense_init, flash_attention, flash_attention_kvscan,
                     rms_norm, split_keys)


# ---------------------------------------------------------------------------
# Parameter initialization
# ---------------------------------------------------------------------------


def init_attn(key, cfg: ModelConfig, n: int) -> Params:
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    ks = split_keys(key, 4)
    dt = cfg.param_dtype
    return {
        "wq": dense_init(ks[0], (n, d, qd), dt, fan_in=d),
        "wk": dense_init(ks[1], (n, d, kvd), dt, fan_in=d),
        "wv": dense_init(ks[2], (n, d, kvd), dt, fan_in=d),
        "wo": dense_init(ks[3], (n, qd, d), dt, fan_in=qd),
        "ln": jnp.zeros((n, d), dt),
    }


def init_mlp(key, cfg: ModelConfig, n: int, d_ff: Optional[int] = None) -> Params:
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    ks = split_keys(key, 3)
    dt = cfg.param_dtype
    p = {
        "wi": dense_init(ks[0], (n, d, ff), dt, fan_in=d),
        "wo": dense_init(ks[1], (n, ff, d), dt, fan_in=ff),
        "ln": jnp.zeros((n, d), dt),
    }
    if cfg.glu:
        p["wg"] = dense_init(ks[2], (n, d, ff), dt, fan_in=d)
    return p


def init_moe(key, cfg: ModelConfig, n: int) -> Params:
    d, e = cfg.d_model, cfg.moe_experts
    ff = cfg.moe_d_ff or cfg.d_ff
    ks = split_keys(key, 5)
    dt = cfg.param_dtype
    p = {
        "router": dense_init(ks[0], (n, d, e), dt, fan_in=d),
        "wi": dense_init(ks[1], (n, e, d, ff), dt, fan_in=d),
        "wo": dense_init(ks[2], (n, e, ff, d), dt, fan_in=ff),
        "ln": jnp.zeros((n, d), dt),
    }
    if cfg.glu:
        p["wg"] = dense_init(ks[3], (n, e, d, ff), dt, fan_in=d)
    if cfg.moe_shared_expert:
        p["shared"] = init_mlp(ks[4], cfg, n, d_ff=ff)
    return p


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _paged_kernel_sharded(kernel, q, store, rows, mesh, data_axes):
    """Run a paged-attention Pallas kernel ``kernel(q, *store, *rows)``
    on a live mesh.

    A ``pallas_call`` is opaque to the SPMD partitioner, so the layout is
    explicit, through ``shard_map_compat`` (the tree's one ``jax.shard_map``
    call site, which the source lint enforces): requests split over the
    data axes when those divide the batch, and query heads over ``model``.
    The page store splits its KV heads over ``model`` when they divide
    evenly; a single KV head (MQA) replicates to every head shard.  Each
    shard then streams only ITS requests' pages for ITS heads.

    ``q`` carries its heads at axis -2; ``store`` holds the page-store
    leaves (pages ``(n_pages, ps, KVH, hd)``, per-page scales ``(n_pages,
    KVH)``); ``rows`` are per-request operands with the batch leading."""
    if mesh is None or getattr(mesh, "empty", False):
        return kernel(q, *store, *rows)
    b, h, kvh = q.shape[0], q.shape[-2], store[0].shape[2]
    bax = tuple(a for a in data_axes if a in mesh.axis_names)
    nb = math.prod(mesh.shape[a] for a in bax)
    nm = mesh.shape["model"] if "model" in mesh.axis_names else 1
    if b % nb:
        bax, nb = (), 1
    if nb == 1 and nm == 1:
        return kernel(q, *store, *rows)
    if h % nm or (kvh % nm and kvh != 1):
        raise ValueError(
            f"paged attention splits heads over model={nm}: needs the "
            f"{h} query heads divisible by it and the {kvh} KV heads "
            f"divisible by it (or a single KV head)")
    heads = "model" if nm > 1 else None
    kv_heads = kv_head_axis(kvh, mesh)
    q_spec = P(bax or None, *([None] * (q.ndim - 3)), heads, None)
    store_specs = tuple(P(None, None, kv_heads, None) if x.ndim == 4
                        else P(None, kv_heads) for x in store)
    return shard_map_compat(
        kernel, mesh=mesh,
        in_specs=(q_spec,) + store_specs + (P(bax or None),) * len(rows),
        out_specs=q_spec, check_vma=False)(q, *store, *rows)


def attn_forward(p: Params, x: jax.Array, cfg: ModelConfig, *,
                 positions: jax.Array,
                 cache: Optional[Dict[str, jax.Array]] = None,
                 cache_len: Optional[jax.Array] = None,
                 mesh=None, data_axes: Tuple[str, ...] = (),
                 seqshard: bool = False, keep_seq_sharded: bool = False,
                 pages: Optional[jax.Array] = None,
                 new_lens: Optional[jax.Array] = None,
                 ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """x: (B, S, d).  If ``cache`` is given (decode), S == 1 and the new K/V
    are written at position ``cache_len``; returns the updated cache.

    Paged mode (``pages`` given): ``cache`` is the KV pool's page store
    ``{"k"/"v": (n_pages, page_size, KVH, hd)}`` shared by every request;
    ``pages`` is each request's (B, P) page-index vector and position ``t``
    lives at ``pages[b, t // page_size]`` offset ``t % page_size``.  The
    chunk's K/V are scattered into the pages in place and attention reads
    by page index — S == 1 through the streaming decode kernel, S > 1
    (chunked prefill, right-aligned with ``new_lens`` valid trailing
    tokens per row) through the streaming chunk-prefill kernel.  A store that
    also carries ``k_scale``/``v_scale`` leaves is the QUANTIZED pool
    (int8 pages + per-(page, KV head) float32 scales, ``kernels.quant``):
    writes go through ``requant_scatter`` and attention through the
    in-kernel-dequant kernel variants."""
    B, S, d = x.shape
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    q = (h @ p["wq"]).reshape(B, S, cfg.n_heads, cfg.hd)
    k = (h @ p["wk"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    v = (h @ p["wv"]).reshape(B, S, cfg.n_kv_heads, cfg.hd)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)

    if pages is not None and "k_scale" in cache:
        # quantized page store (``kernels.quant`` layout): merge the
        # chunk's K/V into the touched pages via dequant -> scatter ->
        # requant (shared prefix pages sit below the touched window and
        # are never rewritten — the COW contract at byte level), then
        # attend through the in-kernel-dequant variants
        from ..kernels.quant import requant_scatter
        kc, vc, ksc, vsc = requant_scatter(
            cache["k"], cache["v"], cache["k_scale"], cache["v_scale"],
            k, v, pages, cache_len, new_lens)
        if S == 1 and new_lens is None:
            o = _paged_kernel_sharded(
                K.paged_attention_quant, q[:, 0], (kc, vc, ksc, vsc),
                (pages, cache_len), mesh, data_axes)[:, None]
        else:
            nl = new_lens if new_lens is not None \
                else jnp.full((B,), S, jnp.int32)
            o = _paged_kernel_sharded(
                K.paged_chunk_attention_quant, q, (kc, vc, ksc, vsc),
                (pages, cache_len, nl), mesh, data_axes)
        new_cache = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
    elif pages is not None:
        # paged data plane: scatter the chunk's K/V into the shared page
        # store, then attend by page index — the dense (B, S, KVH, hd)
        # cache never materializes on the decode path
        n_pages, ps = cache["k"].shape[0], cache["k"].shape[1]
        n_lanes = pages.shape[1]
        t_new = cache_len[:, None] - S + jnp.arange(S)[None, :]     # (B, S)
        valid_new = t_new >= 0
        if new_lens is not None:    # right-aligned chunk: leading pad cols
            valid_new &= jnp.arange(S)[None, :] >= S - new_lens[:, None]
        col = jnp.clip(t_new, 0, n_lanes * ps - 1)
        page = jnp.take_along_axis(pages, col // ps, axis=1)        # (B, S)
        page = jnp.where(valid_new & (page >= 0), page, n_pages)    # -> drop
        off = col % ps
        kc = cache["k"].at[page, off].set(k.astype(cache["k"].dtype),
                                          mode="drop")
        vc = cache["v"].at[page, off].set(v.astype(cache["v"].dtype),
                                          mode="drop")
        if S == 1 and new_lens is None:
            o = _paged_kernel_sharded(
                K.paged_attention, q[:, 0], (kc, vc), (pages, cache_len),
                mesh, data_axes)[:, None]
        else:
            nl = new_lens if new_lens is not None \
                else jnp.full((B,), S, jnp.int32)
            o = _paged_kernel_sharded(
                K.paged_chunk_attention, q, (kc, vc), (pages, cache_len, nl),
                mesh, data_axes)
        new_cache = {"k": kc, "v": vc}
    elif cache is None:
        if seqshard and mesh is not None:
            # heads %% TP != 0: shard the q sequence over "model" instead of
            # heads; K/V (small under GQA) replicate (DESIGN.md §5)
            from jax.sharding import NamedSharding
            bax = tuple(a for a in data_axes if a in mesh.axis_names) or None
            q = jax.lax.with_sharding_constraint(
                q, NamedSharding(mesh, P(bax, "model", None, None)))
            k = jax.lax.with_sharding_constraint(
                k, NamedSharding(mesh, P(bax, None, None, None)))
            v = jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, P(bax, None, None, None)))
            o = flash_attention_kvscan(q, k, v, causal=cfg.causal,
                                       block_kv=cfg.attn_block_kv)
            o = jax.lax.with_sharding_constraint(
                o, NamedSharding(mesh, P(bax,
                                         "model" if keep_seq_sharded
                                         else None, None, None)))
        else:
            o = flash_attention(q, k, v, causal=cfg.causal,
                                block_q=cfg.attn_block_q,
                                block_kv=cfg.attn_block_kv)
        new_cache = {"k": k, "v": v}
    elif cache_len.ndim == 0:
        # uniform-length batch (the dry-run serve_step contract): a single
        # dynamic-update-slice on the (possibly sequence-sharded) cache —
        # partitions cleanly, unlike a per-batch scatter
        pos = cache_len - 1
        kc = lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype)[:, :1], (0, pos, 0, 0))
        vc = lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype)[:, :1], (0, pos, 0, 0))
        o = decode_attention(q, kc, vc,
                             jnp.full((B,), cache_len, jnp.int32))
        new_cache = {"k": kc, "v": vc}
    else:
        idx = cache_len[:, None] - 1 + jnp.zeros((B, 1), jnp.int32)
        bidx = jnp.arange(B)[:, None]
        kc = cache["k"].at[bidx, idx].set(k.astype(cache["k"].dtype))
        vc = cache["v"].at[bidx, idx].set(v.astype(cache["v"].dtype))
        o = decode_attention(q, kc, vc, cache_len)
        new_cache = {"k": kc, "v": vc}
    out = o.reshape(B, S, cfg.q_dim) @ p["wo"]
    return out.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# Dense MLP
# ---------------------------------------------------------------------------


def mlp_forward(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = rms_norm(x, p["ln"], cfg.norm_eps)
    a = act_fn(cfg.act)(h @ p["wi"])
    if cfg.glu:
        a = a * (h @ p["wg"])
    return (a @ p["wo"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# Mixture-of-Experts with explicit expert parallelism (shard_map + all_to_all)
# ---------------------------------------------------------------------------


def _moe_local(x, router, wi, wg, wo, cfg: ModelConfig,
               model_axis: str, n_model: int,
               weight_resident_axes: Tuple[str, ...] = ()):
    """Per-device MoE body (runs inside shard_map).

    x: (T_loc, d) local tokens.  Experts are sharded over ``model_axis``
    (E_loc = E / n_model per device).  Dispatch: local top-k + capacity
    bucketing into an (E, c, d) send buffer, all_to_all over the model axis,
    expert matmuls on (E_loc, n_model*c, d), reverse all_to_all, weighted
    combine.  This is GShard/DeepSpeed-style EP mapped onto jax.lax
    collectives (DESIGN.md §2: communication pattern -> jax-native).
    """
    T, d = x.shape
    E, k = cfg.moe_experts, cfg.moe_top_k
    e_loc = E // n_model
    cap = max(1, math.ceil(T * k * cfg.capacity_factor / E))

    logits = x @ router                                   # (T, E)
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = lax.top_k(probs, k)                    # (T, k)
    if k > 1:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = top_e.reshape(-1)                            # (T*k,)
    flat_p = top_p.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(T), k)
    # position of each (token, choice) within its expert's capacity bucket
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)   # (T*k, E)
    pos = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, 0)

    send = jnp.zeros((E, cap, d), x.dtype)
    send = send.at[flat_e, safe_pos].add(
        jnp.where(keep[:, None], x[flat_t], 0).astype(x.dtype))
    # exchange: device i receives, from every peer j, j's buffer slice for
    # i's local experts -> (n_model, e_loc, cap, d), axis 0 = source device
    recv = lax.all_to_all(send, model_axis, split_axis=0, concat_axis=0,
                          tiled=True)
    recv = recv.reshape(n_model, e_loc, cap, d).transpose(1, 0, 2, 3)
    recv = recv.reshape(e_loc, n_model * cap, d)

    if weight_resident_axes:
        # Weight-resident EP (beyond-paper optimization, §Perf): expert
        # weights stay sharded (E over model, d_ff over the data axes) and
        # ACTIVATIONS move instead.  Order matters: the a2a dispatch above
        # ran on LOCAL tokens; only the post-dispatch per-expert inputs are
        # gathered over the data axes so every ff-shard sees the full token
        # set (gather-before-dispatch would make every data rank send an
        # identical, x n_data redundant a2a — §Perf iteration 4).
        rows0 = recv.shape[1]
        for ax in weight_resident_axes:
            recv = lax.all_gather(recv, ax, axis=1, tiled=True)

    a = act_fn(cfg.act)(jnp.einsum("ecd,edf->ecf", recv, wi,
                                   preferred_element_type=jnp.float32))
    if cfg.glu:
        a = a * jnp.einsum("ecd,edf->ecf", recv, wg,
                           preferred_element_type=jnp.float32)
    out = jnp.einsum("ecf,efd->ecd", a.astype(x.dtype), wo)
    if weight_resident_axes:
        # complete the d_ff contraction across the ff shards, then keep only
        # this device's token rows (last-gathered axis is outermost)
        out = lax.psum(out, weight_resident_axes)
        didx = 0
        for ax in reversed(weight_resident_axes):
            didx = didx * axis_size(ax) + lax.axis_index(ax)
        out = lax.dynamic_slice_in_dim(out, didx * rows0, rows0, axis=1)

    out = out.reshape(e_loc, n_model, cap, d).transpose(1, 0, 2, 3)
    back = lax.all_to_all(out.reshape(E, cap, d), model_axis,
                          split_axis=0, concat_axis=0, tiled=True)
    gathered = back[flat_e, safe_pos]                     # (T*k, d)
    gathered = jnp.where(keep[:, None], gathered, 0)
    y = jnp.zeros((T, d), jnp.float32)
    y = y.at[flat_t].add(gathered.astype(jnp.float32)
                         * flat_p[:, None].astype(jnp.float32))
    y = y.astype(x.dtype)
    # auxiliary load-balance loss (switch-style)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return y, aux


def _moe_local_tp(x_loc, router, wi, wg, wo, cfg: ModelConfig,
                  data_axes: Tuple[str, ...], n_model: int):
    """Weight-resident decode path (runs inside shard_map).

    Tokens are tiny at decode time, so: all-gather tokens over the data axes
    (a few hundred KB), compute ALL gathered tokens against the local expert
    shard (E over "model", d_ff over "data"), weight by routing probs, and
    psum over (data, model) — one small (T, d) all-reduce instead of
    gathering hundreds of GB of expert weights.
    """
    T_loc, d = x_loc.shape
    E, k = cfg.moe_experts, cfg.moe_top_k
    xg = x_loc
    for ax in data_axes:
        xg = lax.all_gather(xg, ax, axis=0, tiled=True)
    T = xg.shape[0]
    logits = xg @ router
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_p, top_e = lax.top_k(probs, k)
    if k > 1:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    e_loc = wi.shape[0]
    eix = lax.axis_index("model") * e_loc + jnp.arange(e_loc)
    # weight w[t, e_local]: routing prob if chosen else 0
    sel = (top_e[:, None, :] == eix[None, :, None])          # (T, e_loc, k)
    w = jnp.sum(jnp.where(sel, top_p[:, None, :], 0.0), -1)  # (T, e_loc)
    a = act_fn(cfg.act)(jnp.einsum("td,edf->etf", xg, wi))
    if cfg.glu:
        a = a * jnp.einsum("td,edf->etf", xg, wg)
    out = jnp.einsum("etf,efd->etd", a.astype(xg.dtype), wo)  # partial (ff)
    y = jnp.einsum("etd,te->td", out.astype(jnp.float32),
                   w.astype(jnp.float32))
    y = lax.psum(y, ("model",) + tuple(data_axes))
    # slice back to this device's tokens (last-gathered axis is outermost)
    if data_axes:
        didx = 0
        for ax in reversed(data_axes):
            didx = didx * axis_size(ax) + lax.axis_index(ax)
        y = lax.dynamic_slice_in_dim(y, didx * T_loc, T_loc, axis=0)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce)
    return y.astype(x_loc.dtype), aux


def moe_forward(p: Params, x: jax.Array, cfg: ModelConfig, mesh,
                data_axes: Tuple[str, ...], split_tokens_over_model: bool,
                decode_tp: bool = False,
                weight_resident: bool = False) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (B, S, d), aux-loss scalar."""
    B, S, d = x.shape
    model_axis = "model"
    n_model = mesh.shape[model_axis]
    token_axes = data_axes + ((model_axis,) if split_tokens_over_model else ())
    mesh_axes = tuple(mesh.axis_names)

    h = rms_norm(x, p["ln"], cfg.norm_eps).reshape(B * S, d)

    if decode_tp:
        def body(h_loc, router, wi, wg, wo):
            y, aux = _moe_local_tp(h_loc, router, wi, wg, wo, cfg,
                                   data_axes, n_model)
            return y, lax.pmean(aux, mesh_axes)
        in_specs = (P(data_axes or None, None), P(),
                    P(model_axis, None, "data"), P(model_axis, None, "data"),
                    P(model_axis, "data", None))
        out_specs = (P(data_axes or None, None), P())
    elif weight_resident:
        wr_axes = tuple(a for a in data_axes if a in mesh.axis_names)

        def body(h_loc, router, wi, wg, wo):
            y, aux = _moe_local(h_loc, router, wi, wg, wo, cfg,
                                model_axis, n_model,
                                weight_resident_axes=wr_axes)
            return y, lax.pmean(aux, mesh_axes)
        in_specs = (P(token_axes, None), P(),
                    P(model_axis, None, wr_axes or None),
                    P(model_axis, None, wr_axes or None),
                    P(model_axis, wr_axes or None, None))
        out_specs = (P(token_axes, None), P())
    else:
        def body(h_loc, router, wi, wg, wo):
            y, aux = _moe_local(h_loc, router, wi, wg, wo, cfg,
                                model_axis, n_model)
            return y, lax.pmean(aux, mesh_axes)
        in_specs = (P(token_axes, None), P(), P(model_axis, None, None),
                    P(model_axis, None, None), P(model_axis, None, None))
        out_specs = (P(token_axes, None), P())

    args = [h, p["router"], p["wi"], p.get("wg", p["wi"][..., :1]), p["wo"]]
    y, aux = shard_map_compat(body, mesh=mesh, in_specs=in_specs,
                              out_specs=out_specs, check_vma=False)(*args)
    y = y.reshape(B, S, d).astype(x.dtype)
    if "shared" in p:  # always-on shared expert (llama4), outside shard_map
        sh = p["shared"]
        hs = rms_norm(x, sh["ln"], cfg.norm_eps)
        a = act_fn(cfg.act)(hs @ sh["wi"])
        if cfg.glu:
            a = a * (hs @ sh["wg"])
        y = y + (a @ sh["wo"]).astype(x.dtype)
    return y, aux


# ---------------------------------------------------------------------------
# Transformer block
# ---------------------------------------------------------------------------


def block_forward(p: Params, x: jax.Array, cfg: ModelConfig, *,
                  positions, mesh=None, data_axes=("data",),
                  is_moe: bool = False, cache=None, cache_len=None,
                  split_tokens_over_model: bool = True,
                  moe_decode_tp: bool = False,
                  moe_weight_resident: bool = False,
                  attn_seqshard: bool = False,
                  keep_seq_sharded: bool = False,
                  pages=None, new_lens=None):
    a, new_cache = attn_forward(p["attn"], x, cfg, positions=positions,
                                cache=cache, cache_len=cache_len,
                                mesh=mesh, data_axes=tuple(data_axes or ()),
                                seqshard=attn_seqshard,
                                keep_seq_sharded=keep_seq_sharded,
                                pages=pages, new_lens=new_lens)
    x = x + a
    if is_moe:
        m, aux = moe_forward(p["moe"], x, cfg, mesh, data_axes,
                             split_tokens_over_model,
                             decode_tp=moe_decode_tp,
                             weight_resident=moe_weight_resident)
    else:
        m, aux = mlp_forward(p["mlp"], x, cfg), jnp.zeros((), jnp.float32)
    return x + m, aux, new_cache
