"""Seeded random weights, made by the benchmark on the device in one jitted
call, in the dtype and layout they are served in.

The layout is the dense block's parameter tree as the program consumes
it (``layers.attn.{wq,wk,wv,wo,ln}``, ``layers.mlp.{wi,wg,wo,ln}`` stacked
over layers, ``embed``, ``lm_head`` unless tied, ``final_ln``); the
placement is the program's serving layout (``param_specs(decode=True)``).
Matmul weights are N(0, 1/fan_in), but the two projections that write the
residual stream (``attn.wo``, ``mlp.wo``) are N(0, (OUT_GAIN)^2/fan_in);
norm scales are N(0, 0.1^2) so the ``(1 + scale)`` of every norm is
exercised.  The reference reads these same arrays; it never reads
anything the program made.

Why the gain: with a tied head, the logit of the token that was just fed
in rises by about sqrt(d) x (embedding RMS / final residual RMS) standard
deviations.  At unit gain that is ~27 sigma after 4 layers at d = 2304,
so every greedy token would be the input token again and no rounding
could change one.  At gain 16 the 40 layers leave a residual of RMS ~100
against the embedding's 1, the input token's edge falls under one sigma,
and the best two logits lie a few tenths apart, as in a trained model.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

NORM_STD = 0.1
OUT_GAIN = 16.0     # on the projections into the residual stream


def key_of(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (beyond 32 bits)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2**32), seed >> 32)


def shapes(m: Dict) -> Dict:
    """(shape, std) per leaf of the dense block's parameter tree."""
    L, d, ff, V = m["n_layers"], m["d_model"], m["d_ff"], m["vocab"]
    qd, kvd = m["n_heads"] * m["head_dim"], m["n_kv_heads"] * m["head_dim"]
    mlp = {"wi": ((L, d, ff), d ** -0.5),
           "wo": ((L, ff, d), OUT_GAIN * ff ** -0.5),
           "ln": ((L, d), NORM_STD)}
    if m["glu"]:
        mlp["wg"] = ((L, d, ff), d ** -0.5)
    tree = {"final_ln": ((d,), NORM_STD), "embed": ((V, d), d ** -0.5),
            "layers": {"attn": {"wq": ((L, d, qd), d ** -0.5),
                                "wk": ((L, d, kvd), d ** -0.5),
                                "wv": ((L, d, kvd), d ** -0.5),
                                "wo": ((L, qd, d), OUT_GAIN * qd ** -0.5),
                                "ln": ((L, d), NORM_STD)},
                       "mlp": mlp}}
    if not m["tie_embeddings"]:
        tree["lm_head"] = ((d, V), d ** -0.5)
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def make(m: Dict, shardings, seed: int):
    """-> the parameter tree on the device.  ``shardings`` is a callable
    from the tree of ``ShapeDtypeStruct`` to a tree of PartitionSpecs (the
    program's serving layout) and a mesh: ``(specs_fn, mesh)``."""
    dtype = jnp.dtype(m["param_dtype"])
    spec_tree = shapes(m)
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=_is_leaf)
    structs = treedef.unflatten(
        [jax.ShapeDtypeStruct(s, dtype) for s, _ in leaves])
    specs_fn, mesh = shardings
    out_sh = jax.tree.map(lambda p: NamedSharding(mesh, p),
                          specs_fn(structs))

    def init(key):
        out = []
        for i, (shape, std) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            out.append(jax.random.normal(k, shape, dtype) * jnp.asarray(
                std, dtype))
        return treedef.unflatten(out)

    params = jax.jit(init, out_shardings=out_sh)(key_of(seed))
    return jax.block_until_ready(params)
