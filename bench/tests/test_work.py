"""bench/work.py against hand counts at the published widths."""

import pytest

from bench import work

MINICPM = work.Dims(n_layers=40, d_model=2304, n_heads=36, n_kv_heads=36,
                    head_dim=64, d_ff=5760, vocab=122753, glu=True)
GRANITE = work.Dims(n_layers=52, d_model=6144, n_heads=48, n_kv_heads=1,
                    head_dim=128, d_ff=24576, vocab=49152, glu=False)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_matmul_params():
    # q, o: 2304 x 2304 each; k, v: 2304 x 2304 each (MHA); 3 x 2304 x 5760
    assert work.matmul_params(MINICPM) == 40 * (4 * 2304 * 2304
                                                + 3 * 2304 * 5760)
    assert work.matmul_params(MINICPM) == 2_441_871_360
    # q, o: 6144 x 6144; k, v: 6144 x 128 (one KV head); 2 x 6144 x 24576
    assert work.matmul_params(GRANITE) == 52 * (2 * 6144 * 6144
                                                + 2 * 6144 * 128
                                                + 2 * 6144 * 24576)
    assert work.matmul_params(GRANITE) == 19_711_131_648


def test_decode_step_flops():
    one = work.decode_step(MINICPM, [1000])
    assert one == 2 * 2_441_871_360 + 2 * 2304 * 122753 \
        + 4 * 40 * 36 * 64 * 1000
    two = work.decode_step(MINICPM, [1000, 10])
    assert two - one == 2 * 2_441_871_360 + 2 * 2304 * 122753 \
        + 4 * 40 * 36 * 64 * 10


def test_prefill_chunk_flops():
    # 256 tokens after 512 paged ones: contexts 513..768
    f = work.prefill_chunk(GRANITE, 512, 256, head=True)
    ctx = sum(range(513, 769))
    assert f == pytest.approx(2 * 19_711_131_648 * 256
                              + 4 * 52 * 48 * 128 * ctx
                              + 2 * 6144 * 49152)
    assert work.prefill_chunk(GRANITE, 512, 256, head=False) == \
        pytest.approx(f - 2 * 6144 * 49152)


def test_paged_attn_call():
    c = work.paged_attn_call(MINICPM, [1000, 200])
    # K and V of 1200 live tokens x 36 heads x 64 x 2 bytes, q and out
    assert c.bytes == 2 * 36 * 64 * 2 * 1200 + 2 * 2 * 36 * 64 * 2
    assert c.flops == 4 * 36 * 64 * 1200
    t, bound = work.least_time(c, PEAKS)
    assert bound == "memory" and t == pytest.approx(c.bytes / 819e9)
    # four chips: 12 query heads each, the one KV head read by every chip
    g = work.paged_attn_call(GRANITE, [4096], tp=4)
    assert g.bytes == 2 * 1 * 128 * 2 * 4096 + 2 * 12 * 128 * 2
    assert g.flops == 4 * 12 * 128 * 4096


def test_chunk_attn_call():
    c = work.chunk_attn_call(MINICPM, [(512, 256), (0, 100)])
    ctx = sum(range(513, 769)) + sum(range(1, 101))
    assert c.flops == 4 * 36 * 64 * ctx
    assert c.bytes == 2 * 36 * 64 * 2 * (768 + 100) \
        + 2 * 36 * 64 * 2 * (256 + 100)


def test_dims_from_a_configuration_file():
    import json
    from pathlib import Path
    root = Path(__file__).resolve().parents[2]
    m = json.loads((root / "bench/configs/minicpm-2b.json").read_text())
    assert work.Dims.of(m["model"]) == MINICPM
