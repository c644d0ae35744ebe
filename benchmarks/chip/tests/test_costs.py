"""The kernels' operation and byte counts against hand counts at small
shapes."""
from pathlib import Path

import pytest

import run

COSTS = Path(__file__).resolve().parents[1] / "costs"
SIZES = {"feat": 2, "sub_kernel": 2, "stages": [[1, 2, 3, 2]], "vocab": 5}


def cost(name):
    return run.load_module(COSTS / f"{name}.py")


def test_forward_flops_per_window():
    # 8 frames in; front conv 8 x (2 x 3 x 1 x 2) = 96 MACs; subsampled to
    # 4 frames: sub conv 4 x (2 x 2 x 2 x 2) = 64, block conv
    # 4 x (2 x 3 x 2 x 2) = 96, two FCs 2 x 4 x 4^2 = 128, head
    # 4 x (4 x 5) = 80; 464 MACs
    assert cost("tds_forward").flops_per_window(SIZES) == 2 * 464


def test_tds_conv_calls():
    # b = 3 rows, w = 2 windows: 16 frames in
    assert cost("tds_conv").calls(SIZES, 3, 2) == [
        # front: k 3, 1 -> 2 channels, 16 frames out
        (2 * 3 * 16 * 2 * 3 * 1 * 2,
         4 * (3 * 18 * 2 * 1 + 3 * 1 * 2 + 2 + 3 * 16 * 2 * 2)),
        # subsampling: k 2, stride 2, 2 -> 2 channels, 8 frames out
        (2 * 3 * 8 * 2 * 2 * 2 * 2,
         4 * (3 * 17 * 2 * 2 + 2 * 2 * 2 + 2 + 3 * 8 * 2 * 2)),
        # block conv with its residual read: k 3, 8 frames
        (2 * 3 * 8 * 2 * 3 * 2 * 2,
         4 * (3 * 10 * 2 * 2 + 3 * 2 * 2 + 2 + 2 * 3 * 8 * 2 * 2)),
    ]


def test_hypothesis_unit_calls():
    dec = {"beam_size": 4, "max_children": 3}
    # 4 x (2 x 3 + 1) = 28 candidates per row; keys, pb, pnb in; four
    # (row, 4) outputs; one call per decoded frame
    assert cost("hypothesis_unit").calls(SIZES, dec, 3, 2) == [
        (0.0, 4 * (3 * 3 * 28 + 4 * 3 * 4))] * 2


def test_step_shape_from_conv_outputs():
    # front conv (b, 8 w, C, W) keeps every frame; later convs fewer
    shapes = [(3, 16, 2, 2), (3, 8, 2, 2), (3, 8, 2, 2), ()]
    assert cost("tds_conv").step_shape(SIZES, shapes) == (3, 2)
    assert cost("tds_conv").step_shape(SIZES, [()]) is None


def test_step_mfu_reads_the_benchmarks_own_audio_count():
    from types import SimpleNamespace
    view = SimpleNamespace(
        measured={"trace_audio_s": 0.8}, sizes=SIZES,
        trace=SimpleNamespace(chips=1, window_s=2.0),
        peak={"bf16_flops_per_s": 1e3}, cost=cost)
    mfu = run.load_module(COSTS.parent / "metrics" / "step_mfu.bulk.py")
    # 0.8 s of audio = 10 windows of 928 FLOP over 2 s at 1000 FLOP/s
    assert mfu.read(view) == pytest.approx(100.0 * 10 * 928 / 2000)
    view.measured = {}
    assert mfu.read(view) is None
