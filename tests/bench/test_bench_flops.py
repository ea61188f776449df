"""The yardstick's arithmetic against hand counts at small shapes, and
the peaks table."""

import pytest

from bench import drive, flops, measured, run

# 2 query heads over 1 KV head of 4, width 8, feed-forward 16, 3 layers
DIMS = {"L": 3, "d": 8, "Hq": 2, "Hkv": 1, "D": 4, "F": 16, "V": 10}


def test_decode_attention_counts_live_pages_only():
    f, b = flops.decode_attention([17], DIMS, block=16)
    assert f == 4 * 2 * 4 * 17
    # 17 keys live in 2 pages of 16: K and V, 1 head of 4, 2 bytes; then
    # the query read and the output written (2 heads of 4)
    assert b == 2 * 1 * 4 * 32 * 2 + 2 * 2 * 4 * 2
    # a full page is one page, and nothing depends on the table's width
    assert flops.decode_attention([16], DIMS, 16)[1] == 2 * 4 * 16 * 2 + 32
    f2, b2 = flops.decode_attention([17, 3], DIMS, 16)
    assert f2 == f + 4 * 2 * 4 * 3
    assert b2 == b + 2 * 4 * 16 * 2 + 32


def test_prefill_attention_is_causal_over_the_chunk():
    f, b = flops.prefill_attention(16, 4, DIMS, block=16)
    keys = 4 * 16 + (1 + 2 + 3 + 4)
    assert f == 4 * 2 * 4 * keys
    assert b == 2 * 1 * 4 * 32 * 2 + 2 * 2 * 4 * 4 * 2


def test_model_flops_per_token():
    per_layer = 2 * (8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16)
    assert flops.layer_matmul_flops(DIMS) == per_layer
    assert flops.token_flops(10, DIMS) == 3 * (per_layer + 4 * 2 * 4 * 10)
    assert flops.head_flops(DIMS) == 2 * 8 * 10
    # a chunk is its tokens, each at its own position
    assert flops.chunk_flops(5, 3, DIMS) == pytest.approx(
        sum(flops.token_flops(n, DIMS) for n in (6, 7, 8)))


def _run(work):
    r = measured.Run({}, {"engine": {"block_size": 16}}, {}, DIMS,
                     {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}, 1,
                     [], 0.0, 1.0, 1.0, 0.0)
    r.work = work
    return r


def test_work_of_traced_steps():
    w = drive.StepWork(0.0, 0.1, chunks=[(0, 4)], decode=[[17, 18], [5]],
                       final_chunks=1)
    r = _run([w])
    F, B, least = r.decode_kernel_need()
    f0, b0 = flops.decode_attention([17, 5], DIMS, 16)   # the call's step 0
    f1, b1 = flops.decode_attention([18], DIMS, 16)      # step 1
    assert F == 3 * (f0 + f1) and B == 3 * (b0 + b1)
    assert least == pytest.approx(3 * (max(f0 / 1e12, b0 / 1e9)
                                       + max(f1 / 1e12, b1 / 1e9)))
    assert r.model_flops() == pytest.approx(
        flops.chunk_flops(0, 4, DIMS) + flops.head_flops(DIMS)
        + sum(flops.token_flops(n, DIMS) + flops.head_flops(DIMS)
              for n in (17, 18, 5)))


def test_peaks_are_keyed_by_device_kind():
    v5e = run.peaks_of("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(run.BenchError, match="no peaks"):
        run.peaks_of("TPU v99")
