"""A tiny copy of the benchmark for CPU tests: the chat cell cut to the
minicpm smoke widths, its mix to a few short sessions, in a root of its
own (``BENCHMARK.json`` + ``bench/configs`` + ``bench/traffic``)."""

import json
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

MODEL = {"name": "minicpm-smoke", "n_layers": 2, "d_model": 72,
         "n_heads": 6, "n_kv_heads": 6, "head_dim": 12, "d_ff": 144,
         "vocab": 512, "glu": True, "act": "silu", "tie_embeddings": True,
         "norm_eps": 1e-5, "rope_theta": 10000.0, "param_dtype": "bfloat16"}
SCHED = {"max_slots": 4, "page_size": 8, "max_seq": 160,
         "prefill_chunk": 16, "prefill_rows": 2, "token_budget": 32}
MIX = {"session_rate_per_s": 1.5,
       "system_prompts": {"count": 2, "tokens": 24, "zipf_s": 1.2},
       "gap_s_mean": 1.0,
       "first": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                 "min": 4, "max": 40},
       "answer": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                  "min": 2, "max": 30},
       "followup": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                    "min": 2, "max": 30},
       "max_new": {"dist": "lognormal", "median": 6, "sigma": 0.4,
                   "min": 2, "max": 12},
       "preroll_s": 1.0, "tail_s": 2.0, "drain_s": 30,
       "writers": {"swap_period_s": 1.0, "compact_period_s": 0.7}}
# the program's bf16 path and the float32 reference agree on every
# greedy token at these widths (gap 0); the float8 control does not
GAP_LIMIT = 0.05


def make_root(root: Path, cell: str = "tiny.chat") -> Path:
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir(parents=True)
    cfg = json.loads((REPO / "bench/configs/minicpm-2b.json").read_text())
    cfg.update(model=MODEL, scheduler=SCHED, pool_pages=96,
               gap_limit=GAP_LIMIT)
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((REPO / "bench/traffic/chat-swap.json").read_text())
    mix.update(MIX)
    (root / "bench/traffic/tiny-chat.json").write_text(json.dumps(mix))
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    b["configs"] = [dict(b["configs"][0], name="tiny",
                         file="bench/configs/tiny.json")]
    b["workloads"] = [dict(b["workloads"][0], name=cell, config="tiny",
                           traffic="tiny-chat")]
    for m in b["per_layer"]:
        m["workloads"] = [cell]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root
