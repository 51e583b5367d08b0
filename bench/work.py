"""Operations and bytes the ALGORITHM needs, computed from shapes.

Counts are of what attention over the live tokens needs, not of what an
implementation walks: a kernel that skips dead page lanes reads against
the same work and its roofline share rises.  Model FLOPs per token are
2 x the non-embedding matmul parameters, plus the LM head at the one
position per sequence whose logits are used, plus attention over the live
context (QK^T and PV: 4 x heads x head_dim per context token per layer).
Columns a program computes and throws away do not count.

``dims`` is the ``model`` group of a configuration file under
``bench/configs/``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Tuple

__all__ = ["Dims", "Cost", "matmul_params", "decode_step", "prefill_chunk",
           "paged_attn_call", "chunk_attn_call", "least_time"]


@dataclasses.dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    glu: bool
    kv_bytes: int = 2         # bf16 page store
    act_bytes: int = 2        # bf16 activations

    @classmethod
    def of(cls, model: Dict) -> "Dims":
        return cls(**{f.name: model[f.name] for f in dataclasses.fields(cls)
                      if f.name in model})

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim


@dataclasses.dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def __add__(self, o: "Cost") -> "Cost":
        return Cost(self.flops + o.flops, self.bytes + o.bytes)


def matmul_params(d: Dims) -> int:
    """Parameters of the layers' matmuls (embedding, head and norms out)."""
    attn = d.d_model * d.q_dim * 2 + d.d_model * d.kv_dim * 2
    mlp = (3 if d.glu else 2) * d.d_model * d.d_ff
    return d.n_layers * (attn + mlp)


def _attn_flops(d: Dims, ctx_sum: float) -> float:
    return 4.0 * d.n_layers * d.n_heads * d.head_dim * ctx_sum


def decode_step(d: Dims, contexts: Iterable[int]) -> float:
    """Model FLOPs of one decode step: one token per row, each row
    attending over its context (prompt + tokens so far, this one
    included)."""
    ctx = list(contexts)
    per_tok = 2.0 * matmul_params(d) + 2.0 * d.d_model * d.vocab
    return per_tok * len(ctx) + _attn_flops(d, sum(ctx))


def prefill_chunk(d: Dims, pos: int, chunk: int, head: bool) -> float:
    """Model FLOPs of one prefill chunk of ``chunk`` tokens after ``pos``
    already-paged ones; ``head``: the chunk ends the prompt, so the LM head
    runs at its last position."""
    ctx_sum = chunk * pos + chunk * (chunk + 1) / 2.0
    return (2.0 * matmul_params(d) * chunk + _attn_flops(d, ctx_sum)
            + (2.0 * d.d_model * d.vocab if head else 0.0))


def _shard(d: Dims, tp: int) -> Tuple[int, int]:
    """(query heads, KV heads) one chip holds when heads split over ``tp``
    chips; a KV head count that does not divide is replicated."""
    kvh = d.n_kv_heads // tp if d.n_kv_heads % tp == 0 else d.n_kv_heads
    return d.n_heads // tp, kvh


def paged_attn_call(d: Dims, contexts: Iterable[int], tp: int = 1) -> Cost:
    """One decode-attention kernel call (one layer, every live row), on one
    chip: the live tokens' K and V, the query and the output."""
    h, kvh = _shard(d, tp)
    ctx = list(contexts)
    kv = 2.0 * kvh * d.head_dim * d.kv_bytes * sum(ctx)
    qo = 2.0 * h * d.head_dim * d.act_bytes * len(ctx)
    return Cost(4.0 * h * d.head_dim * sum(ctx), kv + qo)


def chunk_attn_call(d: Dims, rows: Iterable[Tuple[int, int]],
                    tp: int = 1) -> Cost:
    """One chunked-prefill attention kernel call (one layer) over real rows
    ``(pos, chunk)``, on one chip: causal attention of the chunk's queries
    over the pos + chunk live tokens."""
    h, kvh = _shard(d, tp)
    flops = by = 0.0
    for pos, chunk in rows:
        flops += 4.0 * h * d.head_dim * (chunk * pos
                                         + chunk * (chunk + 1) / 2.0)
        by += (2.0 * kvh * d.head_dim * d.kv_bytes * (pos + chunk)
               + 2.0 * h * d.head_dim * d.act_bytes * chunk)
    return Cost(flops, by)


def least_time(c: Cost, peaks: Dict) -> Tuple[float, str]:
    """Least seconds the chip needs for ``c``, and which bound sets it."""
    tc = c.flops / peaks["bf16_flops_per_s"]
    tm = c.bytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
