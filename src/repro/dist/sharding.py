"""Mesh-rule sharding: one compact per-arch record drives every placement.

This module is the single source of truth for how arrays are laid out on a
device mesh.  Everything else in the tree (configs, models, training,
serving, fault-tolerance, launch dry-run) talks to it through a small,
stable API:

``MeshRules``
    Frozen per-architecture knob record (the configs' hillclimb surface).
    ``MeshRules()`` is always valid: every field has a default, and every
    derived spec degrades to replication when an axis is missing from the
    mesh or a dimension is not divisible by it.

``logical_to_spec(rules, mesh, axes)``
    Map logical axis names to a ``PartitionSpec``.  Logical names:

    * ``"batch"``     -> the tuple of data-parallel axes present in the
      mesh (``rules.batch`` filtered; e.g. ``("pod", "data")`` on the
      multi-pod mesh, ``("data",)`` on a single pod).
    * ``"fsdp"``      -> ``rules.fsdp`` (weight-storage axis, default
      ``"data"``; ``None`` disables FSDP).
    * ``"seq_model"`` -> ``"model"`` when ``rules.residual_seq`` keeps the
      residual stream sequence-sharded, else ``None``.
    * any mesh axis name -> itself; axes absent from the mesh are silently
      dropped (mapped to ``None``), so the same rules run on 1-device CPU
      meshes and 512-chip pods.

``param_specs(pshape, rules, mesh, decode=False)``
    Per-leaf ``PartitionSpec`` tree for a parameter (shape) tree.  Weight
    matrices are tensor-parallel over ``"model"`` on their flattened
    output/input dim (column- and row-parallel respectively) and
    FSDP-sharded over ``rules.fsdp``; MoE expert weights shard experts over
    ``"model"`` and (when ``moe_weight_resident``) ``d_ff`` over the data
    axes; ``decode=True`` drops FSDP (weight-resident serving) and pins the
    expert layout to the decode shard_map contract (E over ``"model"``,
    ``d_ff`` over ``"data"``).

``cache_specs(cshape, rules, mesh, seq_axes=())``
    Specs for decode caches: batch dim over the data axes, the (large)
    KV sequence dim over ``seq_axes``.

``zero1_specs(pspecs, pshape, mesh)``
    ZeRO-1 optimizer-moment specs: params' specs plus a ``"data"`` shard on
    the first free divisible dim when the param spec carries no data axis.

``batch_spec(rules, mesh, shape)`` / ``_divisible(spec, shape, mesh)``
    Input-batch spec helper, and the divisibility guard every public entry
    point funnels through: any spec entry whose mesh-axis product does not
    divide the dimension is replaced by ``None`` (replication) rather than
    erroring.

``constrain(x, rules, mesh, *axes)`` / ``constrain_layer_params(...)``
    ``with_sharding_constraint`` wrappers over logical axes (no-ops when
    ``mesh`` is ``None`` or empty).  ``constrain_layer_params`` re-asserts
    the FSDP storage sharding on per-layer params inside scanned stacks so
    XLA does not keep whole gathered layers live across the scan.

Like the paper's visible-readers table — which diffuses reader state over a
shared array so coherence traffic spreads NUMA-friendly instead of
hammering one counter — the rules here spread the hot state (params,
moments, caches) across mesh axes while keeping the per-arch record itself
a few bytes.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = [
    "MeshRules", "logical_to_spec", "param_specs", "cache_specs",
    "zero1_specs", "batch_spec", "constrain", "constrain_layer_params",
    "kv_head_axis", "page_store_specs", "axis_size", "shard_map_compat",
    "hierarchical_psum",
]


# ---------------------------------------------------------------------------
# Rules
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshRules:
    """Per-architecture sharding knobs (see the configs for rationale)."""

    batch: Tuple[str, ...] = ("pod", "data")  # logical "batch" axes, in order
    fsdp: Optional[str] = "data"     # weight-storage shard axis; None = off
    tp_weights: bool = True          # TP-shard weight matrices over "model"
    shard_heads: bool = True         # head-sharded attention activations
    shard_kv_heads: bool = False     # TP-shard wk/wv (GQA K/V is small)
    attn_impl: str = "flash"         # "flash" | "seqshard" (heads % TP != 0)
    residual_seq: bool = False       # residual stream stays (B, S/model, d)
    split_moe_tokens: bool = True    # MoE dispatch splits tokens over model
    moe_weight_resident: bool = True  # expert d_ff sharded over data axes

    def batch_axes(self, mesh: Mesh) -> Tuple[str, ...]:
        """The data-parallel axes actually present in ``mesh``."""
        return tuple(a for a in self.batch if a in mesh.axis_names)


# ---------------------------------------------------------------------------
# Axis resolution + divisibility guard
# ---------------------------------------------------------------------------


def _axis_size(mesh: Mesh, names) -> int:
    if names is None:
        return 1
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def _resolve(rules: MeshRules, mesh: Mesh, name):
    names = mesh.axis_names
    if name is None:
        return None
    if isinstance(name, (tuple, list)):
        got = tuple(a for a in name if a in names)
        return got if got else None
    if name == "batch":
        got = rules.batch_axes(mesh)
        return got if got else None
    if name == "fsdp":
        return rules.fsdp if rules.fsdp in names else None
    if name == "seq_model":
        return "model" if (rules.residual_seq and "model" in names) else None
    return name if name in names else None


def logical_to_spec(rules: MeshRules, mesh: Mesh,
                    axes: Sequence[Any]) -> P:
    """Map logical axis names to a PartitionSpec, dropping missing axes."""
    return P(*[_resolve(rules, mesh, a) for a in axes])


def _divisible(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """Replicate (None out) any spec dim the mesh axes don't divide."""
    entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
    out = []
    for dim, s in zip(shape, entries):
        n = _axis_size(mesh, s)
        out.append(s if (s is not None and n > 0 and dim % n == 0) else None)
    return P(*out)


# ---------------------------------------------------------------------------
# Activation constraints
# ---------------------------------------------------------------------------


def constrain(x: jax.Array, rules: MeshRules, mesh: Optional[Mesh],
              *axes) -> jax.Array:
    """with_sharding_constraint over logical axes; no-op off-mesh."""
    if mesh is None or getattr(mesh, "empty", False):
        return x
    spec = _divisible(logical_to_spec(rules, mesh, axes), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_layer_params(lp: Any, rules: MeshRules,
                           mesh: Optional[Mesh]) -> Any:
    """Re-assert FSDP/TP storage sharding on one scanned layer's params.

    Inside ``lax.scan`` over a stacked layer dim, XLA is free to keep the
    gathered per-layer weights live; constraining them back to their
    storage specs bounds live memory to one layer's gather."""
    if mesh is None or getattr(mesh, "empty", False):
        return lp
    if not rules.tp_weights and _resolve(rules, mesh, "fsdp") is None:
        return lp
    specs = _spec_tree(lp, rules, mesh, decode=False)
    return jax.tree.map(
        lambda x, s: jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, s)),
        lp, specs, is_leaf=lambda v: hasattr(v, "shape"))


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

# Per-layer vectors / scalars: always replicated.
_REPLICATED = frozenset({
    "ln", "final_ln", "ln1", "ln2", "ln_x", "out_ln",
    "maa_x", "maa_wkvrg", "decay_base", "cm_mk", "cm_mr",
    "a_log", "dt_bias", "d_skip", "bonus", "router",
})
# Column-parallel (in, out): model on the output dim, fsdp on the input dim.
_COL_PARALLEL = frozenset({
    "wq", "wk", "wv", "wi", "wg", "wr", "lm_head",
    "maa_w1", "decay_w1", "cm_k", "cm_r", "in_proj",
})
# Row-parallel (in, out): model on the input dim, fsdp on the output dim.
_ROW_PARALLEL = frozenset({
    "wo", "cm_v", "out_proj", "maa_w2", "decay_w2",
})


def _core_spec(path: Tuple[str, ...], key: str, ndim: int,
               rules: MeshRules, mesh: Mesh, decode: bool):
    """Trailing-dim spec entries for one leaf; leading stack dims -> None."""
    names = mesh.axis_names
    model = "model" if (rules.tp_weights and "model" in names) else None
    fsdp = None if decode else _resolve(rules, mesh, "fsdp")

    in_moe = "moe" in path and "shared" not in path
    if in_moe and key in ("wi", "wg", "wo"):
        # Expert-parallel weights (E, d_in, d_out): E over "model"; with
        # weight-resident EP the ff dim additionally shards over the data
        # axes (training) / exactly "data" (the decode shard_map contract).
        ep = "model" if "model" in names else None
        if decode:
            wr = "data" if "data" in names else None
        else:
            wr = (rules.batch_axes(mesh) or None) \
                if rules.moe_weight_resident else None
        core = (ep, wr, None) if key == "wo" else (ep, None, wr)
        return (None,) * (ndim - 3) + core

    if key in _REPLICATED:
        return (None,) * ndim
    if key == "embed":
        # (vocab, d): the TP head reads it transposed -> vocab over model
        # (kept even under tp_weights=False: "except the vocab", minicpm).
        m = "model" if "model" in names else None
        return (None,) * (ndim - 2) + (m, fsdp)
    if key == "lora_a":
        return (None,) * (ndim - 2) + (fsdp, None)
    if key == "lora_b":
        return (None,) * (ndim - 2) + (None, model)
    if key in ("wk", "wv") and any(a in ("attn", "shared_attn")
                                   for a in path):
        # GQA/MQA K/V projections are small; TP-shard only when the rules
        # say the kv heads split cleanly.
        m = model if rules.shard_kv_heads else None
        return (None,) * (ndim - 2) + (fsdp, m)
    if key in _COL_PARALLEL:
        return (None,) * (ndim - 2) + (fsdp, model)
    if key in _ROW_PARALLEL:
        return (None,) * (ndim - 2) + (model, fsdp)
    # Unknown leaf: stacked weights (>=3 dims) get the generic column
    # layout on their trailing matmul dims; vectors replicate.
    if ndim >= 3:
        return (None,) * (ndim - 2) + (fsdp, model)
    return (None,) * ndim


def _spec_tree(tree: Any, rules: MeshRules, mesh: Mesh, decode: bool,
               path: Tuple[str, ...] = ()) -> Any:
    if isinstance(tree, dict):
        return {k: _spec_tree(v, rules, mesh, decode, path + (k,))
                for k, v in tree.items()}
    shape = tuple(tree.shape)
    key = path[-1] if path else ""
    core = _core_spec(path, key, len(shape), rules, mesh, decode)
    return _divisible(P(*core), shape, mesh)


def param_specs(pshape: Any, rules: MeshRules, mesh: Mesh,
                decode: bool = False) -> Any:
    """PartitionSpec tree for a parameter (shape) tree.

    ``decode=True`` derives the serving layout: FSDP off (weights resident),
    MoE experts pinned to the decode shard_map contract."""
    return _spec_tree(pshape, rules, mesh, decode)


# ---------------------------------------------------------------------------
# Optimizer-state and cache specs
# ---------------------------------------------------------------------------


def zero1_specs(pspecs: Any, pshape: Any, mesh: Mesh) -> Any:
    """ZeRO-1 moment specs: add a "data" shard where params carry none."""
    if "data" not in mesh.axis_names:
        return pspecs
    nd = mesh.shape["data"]

    def one(spec: P, leaf) -> P:
        shape = tuple(leaf.shape)
        entries = list(tuple(spec)) + [None] * (len(shape) - len(tuple(spec)))
        used = set()
        for e in entries:
            for a in (e if isinstance(e, tuple) else (e,)):
                if a:
                    used.add(a)
        if "data" in used:
            return P(*entries)
        for i, (dim, e) in enumerate(zip(shape, entries)):
            if e is None and dim >= nd and dim % nd == 0:
                entries[i] = "data"
                break
        return P(*entries)

    return jax.tree.map(one, pspecs, pshape,
                        is_leaf=lambda x: isinstance(x, P))


# Decode-cache leaves: core (unstacked) rank, and — for attention KV —
# the sequence dim's position within the core.  Batch is core dim 0.
_CACHE_CORE = {
    "k": (4, 1),        # (B, S, KVH, hd)
    "v": (4, 1),
    "shift1": (2, None),  # (B, d)
    "shift2": (2, None),
    "state": (4, None),   # (B, H, K, V) / (B, nh, ds, hd)
    "conv": (3, None),    # (B, conv-1, d_inner)
}


def cache_specs(cshape: Any, rules: MeshRules, mesh: Mesh,
                seq_axes: Sequence[str] = ()) -> Any:
    """Specs for decode caches: batch over the data axes, the (large) KV
    sequence dim over ``seq_axes`` (e.g. ``("model",)``; ``("data",
    "model")`` for B==1 long-context decode)."""
    bax = rules.batch_axes(mesh) or None
    seq = tuple(a for a in seq_axes if a in mesh.axis_names)

    def one(path: Tuple[str, ...], leaf) -> P:
        shape = tuple(leaf.shape)
        key = path[-1] if path else ""
        core_ndim, seq_at = _CACHE_CORE.get(key, (None, None))
        if core_ndim is None or len(shape) < core_ndim:
            return P(*([None] * len(shape)))
        entries = [None] * len(shape)
        b_at = len(shape) - core_ndim
        entries[b_at] = bax
        if seq_at is not None:
            # never double-book an axis already used for the batch dim
            sq = tuple(a for a in seq if a not in (bax or ()))
            entries[b_at + seq_at] = sq or None
        return _divisible(P(*entries), shape, mesh)

    def walk(node, path=()):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        return one(path, node)

    return walk(cshape)


def kv_head_axis(n_kv_heads: int, mesh: Mesh) -> Optional[str]:
    """The mesh axis the paged KV store splits its KV heads over:
    ``"model"`` when it is live and divides them, else ``None`` (the store
    replicates — e.g. a single MQA KV head serves every head shard)."""
    if "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        return None
    return "model" if n_kv_heads % mesh.shape["model"] == 0 else None


def page_store_specs(store: Any, n_kv_heads: int, mesh: Mesh) -> Any:
    """Specs for the paged KV store (``models.model.init_paged_caches``):
    lane-dense pages ``(L, n_pages, ps, KVH * hd)`` split their lane dim
    and per-page scales ``(L, n_pages, KVH)`` their KV-head dim per
    :func:`kv_head_axis` (a KV head's lanes are contiguous, so an even
    split hands each shard whole KV heads), everything else replicates —
    the pool is shared by every request, so it never splits over the data
    axes."""
    ax = kv_head_axis(n_kv_heads, mesh)
    return {k: P(None, None, None, ax) if x.ndim == 4
            else P(None, None, ax) for k, x in store.items()}


def batch_spec(rules: MeshRules, mesh: Mesh, shape: Sequence[int]) -> P:
    """Spec for a (B, ...) input leaf: batch axes on dim 0, rest replicated."""
    bax = rules.batch_axes(mesh) or None
    return _divisible(P(bax, *([None] * (len(shape) - 1))), tuple(shape),
                      mesh)


# ---------------------------------------------------------------------------
# shard_map entry point
# ---------------------------------------------------------------------------


def axis_size(name: str):
    """Size of a mapped mesh axis inside shard_map."""
    return jax.lax.axis_size(name)


def hierarchical_psum(x, axes: Sequence[str]):
    """Topology-aware all-reduce: psum one mesh axis at a time, innermost
    (fastest interconnect) first.

    ``axes`` is ordered outermost-first, matching mesh axis order — e.g.
    ``("pod", "data")`` reduces within each pod over the ICI "data" axis,
    then combines the per-pod partials over the slow DCN "pod" axis.  A
    single psum over ``("pod", "data")`` would let the compiler pick one
    flat all-reduce spanning both fabrics; staging it keeps the cross-pod
    step down to one scalar/partial per pod (the RMA-locks distribution
    pattern).  Inside ``shard_map`` only."""
    for a in reversed(tuple(axes)):
        x = jax.lax.psum(x, a)
    return x


def shard_map_compat(f, *, mesh, in_specs, out_specs, check_vma=False):
    """``jax.shard_map`` — the one call site the source lint allows, so
    every shard_map in the tree goes through here."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
