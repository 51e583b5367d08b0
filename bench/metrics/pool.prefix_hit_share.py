"""KV pool: share of the prompt tokens of the window's requests that the
prefix cache served (the ``cached`` of each first admission).  Moves
ttft_p50_ms: every prompt token served from the cache is one less to
prefill before the first token."""


def read(ctx):
    reqs = [r for r in ctx.timeline.reqs if r.admit_ns is not None]
    total = sum(r.prompt_len for r in reqs)
    return 100.0 * sum(r.cached for r in reqs) / total if total else None
