"""The plain reference: the dense block the program runs, in float32
``jax.numpy`` at ``default_matmul_precision("highest")``, one layer at a
time, with no kernels, cache or batching.  It imports nothing of the
program and reads only the benchmark's own weights.

The block (every dense configuration here):

    x  = embed[tokens] * sqrt(d)
    per layer:
      h  = rms(x) * (1 + ln_attn)
      q, k, v = h Wq, h Wk, h Wv; RoPE (rotate-half, theta) on q and k
      x += softmax(q k^T / sqrt(hd), causal) v  Wo   (query head i reads
           KV head i // (heads / kv_heads))
      h  = rms(x) * (1 + ln_mlp)
      x += (act(h Wi) [* h Wg]) Wo                   (silu, or tanh-GELU)
    logits = (rms(x) * (1 + final_ln)) E^T  (tied)  or  ... W_head

This is the repo's one Gemma-style block; MiniCPM's and Granite-code's
published blocks differ (see PERF.md, "Departures"), and the reference
follows what the program runs.

The CONTROL is the same forward with every matmul operand rounded to
float8 e4m3 with one scale per tensor (the precision below the bf16 the
configurations state).

The comparison (:func:`widest_gap`): at every position whose next token
was served, the gap by which the served token's reference logit lies
below the reference's best.  Greedy serving puts that gap at 0 up to
rounding near ties.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _f8(a: jax.Array) -> jax.Array:
    """Round to float8 e4m3 with one scale for the tensor, back to f32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(F8).astype(jnp.float32) * s


def _mm(a, b, control: bool):
    if control:
        a, b = _f8(a), _f8(b)
    return a @ b


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def _rope(x, theta):
    """x: (S, H, hd), positions 0..S-1; rotate-half form."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(name):
    return {"silu": jax.nn.silu,
            "gelu": partial(jax.nn.gelu, approximate=True)}[name]


@partial(jax.jit, static_argnames=("m", "control", "block_q"))
def _layer(x, layers, l, *, m, control, block_q):
    """One block on the whole (S, d) float32 residual stream."""
    lp = jax.tree.map(lambda a: a[l].astype(jnp.float32), layers)
    S, d = x.shape
    H, KVH, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a, f = lp["attn"], lp["mlp"]
    h = _rms(x, a["ln"], m["norm_eps"])
    q = _rope(_mm(h, a["wq"], control).reshape(S, H, hd), m["rope_theta"])
    k = _rope(_mm(h, a["wk"], control).reshape(S, KVH, hd), m["rope_theta"])
    v = _mm(h, a["wv"], control).reshape(S, KVH, hd)
    g = H // KVH
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if control:
        k, v = _f8(k), _f8(v)
    nb = S // block_q

    def qblock(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * block_q, block_q, 0)
        if control:
            qi = _f8(qi)
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(hd)
        rows = i * block_q + jnp.arange(block_q)
        s = jnp.where(jnp.arange(S)[None, None, :] <= rows[None, :, None],
                      s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if control:
            p = _f8(p)
        return jnp.einsum("hqk,khd->qhd", p, v)

    o = jax.lax.map(qblock, jnp.arange(nb)).reshape(S, H * hd)
    x = x + _mm(o, a["wo"], control)
    h = _rms(x, f["ln"], m["norm_eps"])
    u = _act(m["act"])(_mm(h, f["wi"], control))
    if m["glu"]:
        u = u * _mm(h, f["wg"], control)
    return x + _mm(u, f["wo"], control)


@partial(jax.jit, static_argnames=("m",))
def _embed(embed, tokens, *, m):
    return embed[tokens].astype(jnp.float32) * math.sqrt(m["d_model"])


@partial(jax.jit, static_argnames=("m", "control"))
def _head(params, x, *, m, control):
    x = _rms(x, params["final_ln"], m["norm_eps"])
    w = (params["embed"].T if m["tie_embeddings"]
         else params["lm_head"]).astype(jnp.float32)
    return _mm(x, w, control)


BUCKET = 1024     # sequence lengths padded to a multiple: few compiles


def forward_logits(params, m: Dict, tokens: np.ndarray,
                   at: Sequence[int], *, control: bool = False,
                   block_q: int = 512) -> np.ndarray:
    """float32 logits (len(at), vocab) at positions ``at`` of ``tokens``.
    The sequence is padded to a multiple of ``BUCKET`` (causal attention
    keeps the padding out of every real position), so one compile serves
    every length in a bucket."""
    mh = _hashable(m)
    S = len(tokens)
    pad = -(-S // BUCKET) * BUCKET
    toks = np.zeros(pad, np.int32)
    toks[:S] = tokens
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(toks), m=mh)
        for l in range(m["n_layers"]):
            x = _layer(x, params["layers"], jnp.int32(l), m=mh,
                       control=control, block_q=block_q)
        xs = x[jnp.asarray(np.asarray(at, np.int32))]
        return np.asarray(_head(params, xs, m=mh, control=control))


class _hashable(dict):
    """The configuration's ``model`` group as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))


def widest_gap(ref_logits: np.ndarray, tokens: Sequence[int]) -> float:
    """max over positions of (best reference logit - reference logit of
    the token put there)."""
    ref = np.asarray(ref_logits, np.float64)
    idx = np.asarray(tokens, np.int64)
    got = ref[np.arange(len(idx)), idx]
    return float(np.max(ref.max(axis=1) - got)) if len(idx) else 0.0


def served_positions(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose next-token logits chose served tokens 0..n-1."""
    return prompt_len - 1 + np.arange(n_served)


def compare(params, m: Dict, sample, *, control: bool = False,
            log=None) -> Dict:
    """Reference over each sampled request's prompt + served tokens.
    ``sample``: objects with ``prompt`` (int32 array) and ``out`` (served
    tokens).  -> {"gap": widest gap of the served tokens, "tokens": n,
    and with ``control`` "control_gap": the same for the tokens the
    float8 control puts first}."""
    worst = ctrl = 0.0
    n = 0
    for r in sample:
        out = np.asarray(r.out, np.int32)
        seq = np.concatenate([np.asarray(r.prompt, np.int32), out[:-1]])
        at = served_positions(len(r.prompt), len(out))
        ref = forward_logits(params, m, seq, at)
        worst = max(worst, widest_gap(ref, out))
        if control:
            c = forward_logits(params, m, seq, at, control=True)
            ctrl = max(ctrl, widest_gap(ref, np.argmax(c, axis=1)))
        n += len(out)
        if log:
            log(f"reference: request {getattr(r, 'rid', '?')} prompt "
                f"{len(r.prompt)} served {len(out)}: widest gap so far "
                f"{worst:.5f}" + (f", control {ctrl:.5f}" if control
                                   else ""))
    res: Dict[str, Optional[float]] = {"gap": worst, "tokens": n}
    if control:
        res["control_gap"] = ctrl
    return res
