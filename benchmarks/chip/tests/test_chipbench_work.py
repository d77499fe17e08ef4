"""work.py counts against numbers worked out by hand for h2o-danube-1.8b
(d_model 2560, 32 heads of 80, 8 KV heads, d_ff 6912, 24 layers, vocab
32000, window 4096)."""
import json

import pytest

from chipbench import harness, work

DANUBE = harness.model_dims(json.loads(
    (harness.HERE / "configs" / "h2o-danube-1.8b.json").read_text()))


def test_danube_dims():
    assert DANUBE["head_dim"] == 80
    assert DANUBE["window"] == 4096


def test_matmul_params():
    # per layer: q, o 2560x2560; k, v 2560x640; wi, wg, wo 2560x6912
    per_layer = 2 * 2560 * 2560 + 2 * 2560 * 640 + 3 * 2560 * 6912
    assert per_layer == 69_468_160
    assert work.matmul_params(DANUBE) == 24 * per_layer + 2560 * 32000
    assert work.matmul_params(DANUBE) == 1_749_155_840


def test_attention_flops_and_window():
    assert work.attn_flops(DANUBE, 1000) == 4 * 32 * 80 * 1000 * 24
    assert work.attended(DANUBE, 99) == 100
    assert work.attended(DANUBE, 9999) == 4096


@pytest.mark.parametrize("rows,kin,n,flops,bytes_", [
    # 2*32*2560*6912; 2560*6912/2 + 64 + 32*2560*2 + 32*6912*4
    (32, 2560, 6912, 1_132_462_080, 9_896_000),
    # a 256-row prefill chunk through wo
    (256, 6912, 2560, 9_059_696_640, 8_847_360 + 64 + 3_538_944 + 2_621_440),
])
def test_lutq_dot_call(rows, kin, n, flops, bytes_):
    assert work.lutq_dot_call(rows, kin, n) == (flops, bytes_)


@pytest.mark.parametrize("ctx,pages", [
    (0, 0),        # dead row: reads nothing
    (1000, 16),    # pages 0..15
    (2560, 40),    # the whole 40-page row
    (5000, 65),    # past the 4096 window: pages 14..78
])
def test_pages_read(ctx, pages):
    assert work.pages_read(ctx, 64, 4096) == pages


def test_paged_attn_step_bytes():
    # one live row at 1000 keys, 64-token pages of 8 KV heads x 80 in
    # bf16, K and V, plus bf16 q and out of 32 x 80
    f, b = work.paged_attn_step(DANUBE, [1000], 64)
    page = 64 * 8 * 80 * 2 * 2
    assert b == 24 * (16 * page + 2 * 32 * 80 * 2)
    assert f == 24 * 4 * 32 * 80 * 1000


def test_model_flops_step():
    n = work.matmul_params(DANUBE)
    head = 2560 * 32000
    # one decode row attending 10 keys; a chunk of 3 prompt tokens at
    # positions 5-7, and a packed segment of 2 at positions 0-1
    want = (2 * n + work.attn_flops(DANUBE, 10)
            + 2 * (n - head) * 3 + 2 * head
            + sum(work.attn_flops(DANUBE, p + 1) for p in (5, 6, 7))
            + 2 * (n - head) * 2 + 2 * head
            + sum(work.attn_flops(DANUBE, p + 1) for p in (0, 1)))
    assert work.model_flops_step(DANUBE, [10], [(5, 3), (0, 2)]) == want


def test_min_time_takes_the_larger_bound():
    pk = harness.peaks_for("TPU v5 lite")
    f, b = work.lutq_dot_call(32, 2560, 6912)
    assert work.min_time(f, b, pk) == pytest.approx(b / 819e9)
    f, b = work.lutq_dot_call(4096, 2560, 6912)
    assert work.min_time(f, b, pk) == pytest.approx(f / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v9 imaginary")
