"""Model assembly: parameter init + train/prefill/decode entry points for
every supported family (dense, moe, ssm=rwkv6, hybrid=zamba2, audio, vlm).

Layer stacks are consumed with ``lax.scan`` over stacked parameters.
Interleaved stacks (llama4 dense/MoE alternation; zamba2's shared attention
block every k mamba layers) scan over *periods*.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from ..dist.sharding import MeshRules, constrain, constrain_layer_params
from .common import ModelConfig, Params, dense_init, rms_norm, split_keys
from .ssm import (CHUNK, init_mamba2, init_rwkv6, mamba2_block, rwkv6_block)
from .transformer import (attn_forward, block_forward, init_attn, init_mlp,
                          init_moe, mlp_forward)

ZAMBA_LORA_RANK = 64


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_params(key: jax.Array, cfg: ModelConfig) -> Params:
    ks = split_keys(key, 12)
    dt = cfg.param_dtype
    L, d = cfg.n_layers, cfg.d_model
    p: Params = {"final_ln": jnp.zeros((d,), dt)}
    if cfg.family != "audio":
        p["embed"] = dense_init(ks[0], (cfg.vocab, d), dt, fan_in=d)
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], (d, cfg.vocab), dt, fan_in=d)

    if cfg.family in ("dense", "audio", "vlm"):
        p["layers"] = {"attn": init_attn(ks[2], cfg, L),
                       "mlp": init_mlp(ks[3], cfg, L)}
    elif cfg.family == "moe":
        k = cfg.moe_every
        np_ = L // k
        layers: Params = {"moe": init_moe(ks[2], cfg, np_)}
        attn = init_attn(ks[3], cfg, L)
        layers["attn"] = jax.tree.map(
            lambda x: x.reshape((np_, k) + x.shape[1:]), attn)
        if k > 1:
            layers["mlp"] = init_mlp(ks[4], cfg, np_ * (k - 1))
            layers["mlp"] = jax.tree.map(
                lambda x: x.reshape((np_, k - 1) + x.shape[1:]),
                layers["mlp"])
        p["layers"] = layers
    elif cfg.family == "ssm":
        p["layers"] = {"rwkv": init_rwkv6(ks[2], cfg, L)}
    elif cfg.family == "hybrid":
        k = cfg.hybrid_attn_every
        np_ = L // k
        mamba = init_mamba2(ks[2], cfg, L)
        p["layers"] = {
            "mamba": jax.tree.map(
                lambda x: x.reshape((np_, k) + x.shape[1:]), mamba),
            # one shared transformer block, reused at every site with
            # per-site LoRA specialization (zamba2)
            "shared_attn": init_attn(ks[3], cfg, 1),
            "shared_mlp": init_mlp(ks[4], cfg, 1),
            "lora_a": dense_init(ks[5], (np_, d, ZAMBA_LORA_RANK), dt),
            "lora_b": dense_init(ks[6], (np_, ZAMBA_LORA_RANK, cfg.q_dim),
                                 dt, fan_in=ZAMBA_LORA_RANK),
        }
    else:
        raise ValueError(cfg.family)
    return p


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_inputs(p: Params, cfg: ModelConfig, batch: Dict[str, jax.Array],
                 rules: MeshRules, mesh: Mesh) -> jax.Array:
    """Assemble the input sequence: [frontend embeddings | token embeddings].

    audio: the whole input is precomputed frame embeddings (stub frontend).
    vlm: ``frontend_tokens`` patch embeddings prefix + text tokens.
    """
    cd = cfg.compute_dtype
    if cfg.family == "audio":
        x = batch["embeds"].astype(cd)
    elif cfg.frontend_tokens and "embeds" in batch:
        tok = p["embed"][batch["tokens"]].astype(cd)
        x = jnp.concatenate([batch["embeds"].astype(cd), tok], axis=1)
    else:
        x = p["embed"][batch["tokens"]].astype(cd)
    if cfg.family != "audio":
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(cd)
    return constrain(x, rules, mesh, "batch", "seq_model", None)


def lm_logits(p: Params, cfg: ModelConfig, x: jax.Array,
              rules: MeshRules, mesh: Mesh) -> jax.Array:
    if rules.residual_seq:
        # vocab is model-sharded: gather the sequence back before the head
        x = constrain(x, rules, mesh, "batch", None, None)
    x = rms_norm(x, p["final_ln"], cfg.norm_eps)
    head = p["embed"].T if cfg.tie_embeddings else p["lm_head"]
    logits = x @ head.astype(x.dtype)
    return constrain(logits, rules, mesh, "batch", None, "model")


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------


def _scan_stack(body, params_stacked, x, cache=None, length=None):
    """Scan ``body(x, layer_params, layer_cache) -> (x, new_cache)``."""
    def f(carry, inp):
        lp, lc = inp
        y, nc = body(carry[0], lp, lc)
        return (y, carry[1]), nc

    (x, _), new_cache = lax.scan(f, (x, jnp.zeros((), jnp.float32)),
                                 (params_stacked, cache), length=length)
    return x, new_cache


def dense_stack(p: Params, x: jax.Array, cfg: ModelConfig, *, positions,
                mesh, rules: MeshRules, caches=None, cache_len=None,
                remat_policy=None, make_caches=True, pages=None,
                new_lens=None):
    """Dense / audio / vlm transformer stack (scan over L layers).

    ``pages``/``new_lens`` (paged serving): caches are the pool's page
    store with a leading layer dim.  The scan CARRIES the whole store and
    scans the layer index beside the weights: each layer writes its K/V
    into the carried store in place and hands the kernels the store and
    its index, so no layer's slice is cut out, relaid out or stacked back.
    The (B, P) page-index matrix is closed over (shared by every layer)."""
    aux_total = jnp.zeros((), jnp.float32)

    def body(x, lp, lc, layer=None):
        if lc is None:   # keep FSDP storage sharding (see dist.sharding)
            lp = constrain_layer_params(lp, rules, mesh)

        def blk(x):
            return block_forward(
                lp, x, cfg, positions=positions, mesh=mesh,
                data_axes=rules.batch_axes(mesh), is_moe=False,
                cache=lc, cache_len=cache_len,
                attn_seqshard=(rules.attn_impl == "seqshard"),
                keep_seq_sharded=rules.residual_seq,
                pages=pages, new_lens=new_lens, layer=layer)
        if remat_policy is not None and lc is None:
            blk = jax.checkpoint(blk, policy=remat_policy)
        y, _, nc = blk(x)
        y = constrain(y, rules, mesh, "batch", "seq_model", None)
        return y, nc

    stacked = {"attn": p["layers"]["attn"], "mlp": p["layers"]["mlp"]}

    if pages is not None:
        def paged(carry, inp):
            (x, store), (lp, layer) = carry, inp
            return body(x, lp, store, layer), None

        (x, store), _ = lax.scan(paged, (x, caches),
                                 (stacked, jnp.arange(cfg.n_layers)))
        return x, aux_total, store

    def f(carry, inp):
        lp, lc = inp
        y, nc = body(carry, {"attn": lp["attn"], "mlp": lp["mlp"]}, lc)
        return y, (nc if (make_caches or lc is not None) else None)

    x, new_caches = lax.scan(f, x, (stacked, caches))
    return x, aux_total, new_caches


def moe_stack(p: Params, x: jax.Array, cfg: ModelConfig, *, positions,
              mesh, rules: MeshRules, caches=None, cache_len=None,
              remat_policy=None, make_caches=True, **_):
    """MoE stack: scan over periods of ``moe_every`` layers; the last layer
    of each period is MoE, the first k-1 are dense."""
    k = cfg.moe_every
    data_axes = rules.batch_axes(mesh)
    split_tok = rules.split_moe_tokens and cache_len is None

    def body(x, lp, lc):
        if lc is None:
            lp = constrain_layer_params(lp, rules, mesh)
        aux = jnp.zeros((), jnp.float32)
        ncs = []
        for j in range(k):
            attn_p = jax.tree.map(lambda a: a[j], lp["attn"])
            is_moe = (j == k - 1)
            sub = {"attn": attn_p}
            if is_moe:
                sub["moe"] = lp["moe"]
            else:
                sub["mlp"] = jax.tree.map(lambda a: a[j], lp["mlp"])
            cj = None if lc is None else jax.tree.map(lambda c: c[j], lc)

            def blk(x, sub=sub, is_moe=is_moe, cj=cj):
                return block_forward(
                    sub, x, cfg, positions=positions, mesh=mesh,
                    data_axes=data_axes, is_moe=is_moe, cache=cj,
                    cache_len=cache_len,
                    split_tokens_over_model=split_tok,
                    moe_decode_tp=(cache_len is not None),
                    moe_weight_resident=(rules.moe_weight_resident
                                         and cache_len is None),
                    attn_seqshard=(rules.attn_impl == "seqshard"))
            if remat_policy is not None and lc is None:
                blk = jax.checkpoint(blk)
            y, a, nc = blk(x)
            x = constrain(y, rules, mesh, "batch", "seq_model", None)
            aux = aux + a
            ncs.append(nc)
        if lc is None and not make_caches:
            return x, aux, None
        nc_stacked = jax.tree.map(lambda *cs: jnp.stack(cs), *ncs)
        return x, aux, nc_stacked

    def f(carry, inp):
        x, aux = carry
        lp, lc = inp
        y, a, nc = body(x, lp, lc)
        return (y, aux + a), nc

    stacked = {"attn": p["layers"]["attn"], "moe": p["layers"]["moe"]}
    if k > 1:
        stacked["mlp"] = p["layers"]["mlp"]
    (x, aux), new_caches = lax.scan(
        f, (x, jnp.zeros((), jnp.float32)), (stacked, caches))
    return x, aux, new_caches


def ssm_stack(p: Params, x: jax.Array, cfg: ModelConfig, *, mesh, rules,
              caches=None, remat_policy=None, chunk: int = CHUNK,
              make_caches=True, **_):
    def f(x, inp):
        lp, lc = inp
        if lc is None:
            lp = constrain_layer_params(lp, rules, mesh)

        def blk(x):
            return rwkv6_block(lp, x, cfg, cache=lc, chunk=chunk)
        if remat_policy is not None and lc is None:
            blk = jax.checkpoint(blk, policy=remat_policy)
        y, nc = blk(x)
        y = constrain(y, rules, mesh, "batch", "seq_model", None)
        return y, (nc if (make_caches or lc is not None) else None)

    x, new_caches = lax.scan(f, x, (p["layers"]["rwkv"], caches))
    return x, jnp.zeros((), jnp.float32), new_caches


def hybrid_stack(p: Params, x: jax.Array, cfg: ModelConfig, *, positions,
                 mesh, rules, caches=None, cache_len=None, remat_policy=None,
                 chunk: int = CHUNK, make_caches=True, **_):
    """zamba2: periods of ``hybrid_attn_every`` mamba2 blocks followed by the
    shared attention+MLP block with per-site LoRA on the q projection."""
    k = cfg.hybrid_attn_every
    shared_attn = jax.tree.map(lambda a: a[0], p["layers"]["shared_attn"])
    shared_mlp = jax.tree.map(lambda a: a[0], p["layers"]["shared_mlp"])

    def f(x, inp):
        lp, lc = inp
        if lc is None:
            lp = constrain_layer_params(lp, rules, mesh)

        def blk(x):
            ncs_m = []
            for j in range(k):
                mp = jax.tree.map(lambda a: a[j], lp["mamba"])
                mc = None if lc is None else \
                    jax.tree.map(lambda c: c[j], lc["mamba"])
                x2, nc = mamba2_block(mp, x, cfg, cache=mc, chunk=chunk)
                x = constrain(x2, rules, mesh, "batch", None, None)
                ncs_m.append(nc)
            # shared attention block w/ per-site LoRA delta on q
            ap = {**shared_attn,
                  "wq": shared_attn["wq"] + lp["lora_a"] @ lp["lora_b"]}
            ac = None if lc is None else lc["attn"]
            a, nc_a = attn_forward(ap, x, cfg, positions=positions,
                                   cache=ac, cache_len=cache_len)
            x = x + a
            x = x + mlp_forward(shared_mlp, x, cfg)
            x = constrain(x, rules, mesh, "batch", None, None)
            nc_m = jax.tree.map(lambda *cs: jnp.stack(cs), *ncs_m)
            return x, nc_m, nc_a

        if remat_policy is not None and lc is None:
            blk = jax.checkpoint(blk)
        x, nc_m, nc_a = blk(x)
        if lc is None and not make_caches:
            nc = None
        else:
            nc = {"mamba": nc_m, "attn": nc_a}
        return x, nc

    stacked = {"mamba": p["layers"]["mamba"], "lora_a": p["layers"]["lora_a"],
               "lora_b": p["layers"]["lora_b"]}
    x, new_caches = lax.scan(f, x, (stacked, caches))
    return x, jnp.zeros((), jnp.float32), new_caches


_STACKS = {"dense": dense_stack, "audio": dense_stack, "vlm": dense_stack,
           "moe": moe_stack, "ssm": ssm_stack, "hybrid": hybrid_stack}


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def forward(p: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            mesh: Mesh, rules: MeshRules, remat_policy=None,
            caches=None, cache_len=None, make_caches=True,
            pages=None, new_lens=None,
            ) -> Tuple[jax.Array, jax.Array, Any]:
    """Full forward pass -> (logits, aux_loss, caches).

    ``pages`` switches attention to the paged-KV data plane: ``caches`` is
    the pool's page store (``serving.kv_pool`` page indices, see
    :func:`init_paged_caches`) and positions are derived per request from
    ``cache_len`` — position of column ``j`` is ``cache_len - S + j``
    (right-aligned chunks; ``new_lens`` marks each row's valid tail)."""
    if pages is not None and cfg.family not in ("dense", "vlm"):
        raise ValueError(f"paged decode supports dense attention caches "
                         f"only (family={cfg.family})")
    x = embed_inputs(p, cfg, batch, rules, mesh)
    S = x.shape[1]
    if cache_len is None:
        positions = jnp.arange(S)[None]
    elif cache_len.ndim == 0:
        positions = (cache_len - 1).reshape(1, 1)
    else:
        # per-request positions for the S right-aligned columns; padded
        # columns clamp to 0 (their K/V and outputs are masked anyway)
        positions = jnp.maximum(
            cache_len[:, None] - S + jnp.arange(S)[None, :], 0)
    stack = _STACKS[cfg.family]
    x, aux, new_caches = stack(p, x, cfg, positions=positions, mesh=mesh,
                               rules=rules, caches=caches,
                               cache_len=cache_len,
                               remat_policy=remat_policy,
                               make_caches=make_caches,
                               pages=pages, new_lens=new_lens)
    logits = lm_logits(p, cfg, x, rules, mesh)
    return logits, aux, new_caches


def loss_fn(p: Params, cfg: ModelConfig, batch: Dict[str, jax.Array], *,
            mesh: Mesh, rules: MeshRules, remat_policy=None,
            aux_weight: float = 0.01) -> Tuple[jax.Array, Dict[str, Any]]:
    logits, aux, _ = forward(p, cfg, batch, mesh=mesh, rules=rules,
                             remat_policy=remat_policy, make_caches=False)
    labels = batch["labels"]
    V = logits.shape[-1]
    logits32 = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(logits32, labels[..., None],
                               axis=-1)[..., 0]
    nll = lse - gold
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(nll)
    # frontend prefix positions carry no labels
    if nll.shape[1] != labels.shape[1]:
        nll = nll[:, -labels.shape[1]:]
    loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = loss + aux_weight * aux
    return total, {"loss": loss, "aux": aux, "nll_mean": loss}


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------


def init_caches(cfg: ModelConfig, batch_size: int, max_seq: int,
                dtype=jnp.bfloat16) -> Any:
    """Zero-initialized decode caches, stacked over the scan dimension."""
    B, S = batch_size, max_seq
    kvh, hd = cfg.n_kv_heads, cfg.hd

    def kv(n):
        return {"k": jnp.zeros((n, B, S, kvh, hd), dtype),
                "v": jnp.zeros((n, B, S, kvh, hd), dtype)}

    if cfg.family in ("dense", "vlm"):
        return kv(cfg.n_layers)
    if cfg.family == "moe":
        k = cfg.moe_every
        n = cfg.n_layers // k
        return {"k": jnp.zeros((n, k, B, S, kvh, hd), dtype),
                "v": jnp.zeros((n, k, B, S, kvh, hd), dtype)}
    if cfg.family == "ssm":
        H = cfg.d_model // 64
        L = cfg.n_layers
        return {"shift1": jnp.zeros((L, B, cfg.d_model), dtype),
                "shift2": jnp.zeros((L, B, cfg.d_model), dtype),
                "state": jnp.zeros((L, B, H, 64, 64), jnp.float32)}
    if cfg.family == "hybrid":
        from .ssm import MAMBA_CONV
        k = cfg.hybrid_attn_every
        n = cfg.n_layers // k
        di = cfg.ssm_expand * cfg.d_model
        ds = cfg.ssm_state
        nh = di // 64
        return {
            "mamba": {
                "conv": jnp.zeros((n, k, B, MAMBA_CONV - 1, di + 2 * ds),
                                  dtype),
                "state": jnp.zeros((n, k, B, nh, ds, 64), jnp.float32)},
            "attn": kv(n),
        }
    raise ValueError(f"{cfg.family} has no decode cache")


def init_paged_caches(cfg: ModelConfig, n_pages: int, page_size: int,
                      dtype=jnp.bfloat16, quantized: bool = False) -> Any:
    """Zero-initialized page STORE for the paged decode path: one pool of
    ``n_pages`` KV pages shared by every request, with a leading layer dim
    that the layer scan carries whole.  The (request -> pages) map lives
    in ``serving.kv_pool.KVPool``; requests address the store through
    their (B, P) page-index vectors.

    A page is lane-dense, ``(page_size, KVH * hd)``: the KV heads sit side
    by side on the lanes, KV head ``j`` on ``[j * hd, (j + 1) * hd)``.
    That row-major layout has no tile padding, so it is the layout the
    store has at rest, the one the kernels read and the one each step
    writes in place: XLA never relays the store out.

    ``quantized=True`` stores pages int8 with float32 per-(page, KV head)
    scales (``kernels.quant`` layout) as sibling leaves ``k_scale`` /
    ``v_scale`` of shape (n_layers, n_pages, KVH): the layer scan carries
    them alongside the content, step donation covers them, and the
    engine's COW page copy moves content + scale as one unit."""
    if cfg.family not in ("dense", "vlm"):
        raise ValueError(f"paged caches need dense attention "
                         f"(family={cfg.family})")
    kvh, hd = cfg.n_kv_heads, cfg.hd
    shape = (cfg.n_layers, n_pages, page_size, kvh * hd)
    if quantized:
        return {"k": jnp.zeros(shape, jnp.int8),
                "v": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:2] + (kvh,), jnp.float32),
                "v_scale": jnp.zeros(shape[:2] + (kvh,), jnp.float32)}
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
