"""The yardstick's arithmetic: work counts, peaks, percentiles."""

import pytest

from bench import peaks, work


def test_linalg_flops():
    assert work.linalg_flops("matmul", 10) == 2000.0
    assert work.linalg_flops("trsm", 10) == 1000.0
    assert work.linalg_flops("cholesky", 30) == pytest.approx(9000.0)
    # one round of the mix at n = 16384: 14.66e12 useful FLOP
    n = 16384
    assert sum(work.linalg_flops(op, n) for op in
               ("matmul", "trsm", "cholesky")) == pytest.approx(1.4660e13,
                                                                rel=1e-3)


def test_matmul_kernel_work_counts_block_reuse():
    tiles = {"bm": 512, "bn": 1024, "bk": 256}
    flops, nbytes = work.matmul_kernel_work(2048, 2048, 2048, tiles)
    assert flops == 2 * 2048 ** 3
    # A read once per column of C blocks (2), B once per row (4), C once
    a, b, c = 2048 * 2048 * 2, 2048 * 2048 * 4, 2048 * 2048
    assert nbytes == 4 * (a + b + c)
    # blocks are capped at the 128-padded extent of small dimensions
    _, small = work.matmul_kernel_work(100, 128, 100, tiles)
    assert small == 4 * (128 * 128 + 128 * 128 + 128 * 128)


def test_hlo_shapes_reads_a_traced_custom_call():
    name = ('%matmul.2 = f32[2048,1024]{1,0:T(8,128)S(1)} custom-call('
            'f32[2048,128]{1,0:T(8,128)} %a.1, f32[128,1024]{1,0:T(8,128)} '
            '%b.1), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={f32[2048,128]{1,0}}')
    out, ops = work.hlo_shapes(name)
    assert out == ("f32", (2048, 1024))
    assert ops == [("f32", (2048, 128)), ("f32", (128, 1024))]
    assert work.itemsize("bf16") == 2


def test_decoder_flops():
    cfg = {"d_model": 4, "head_dim": 2, "n_heads": 2, "n_kv_heads": 1,
           "d_ff": 8, "gated_mlp": False, "n_layers": 3, "vocab_size": 10}
    per_layer = 4 * 2 * 2 * 2 + 4 * 1 * 2 * 2 + 4 * 8 * 2
    params = 3 * per_layer + 4 * 10
    assert work.decoder_matmul_params(cfg) == params
    attn = 4.0 * 3 * 2 * 2
    assert work.decoder_token_flops(cfg, [0, 5]) == pytest.approx(
        2 * params * 2 + attn * (1 + 6))


def test_percentile_nearest_rank():
    xs = list(range(1, 11))
    assert work.percentile(xs, 90) == 9
    assert work.percentile(xs, 100) == 10
    assert work.percentile([3.0], 90) == 3.0
    with pytest.raises(ValueError):
        work.percentile([], 50)


def test_peaks_are_published_and_unknown_kinds_fail():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
