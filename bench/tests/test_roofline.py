"""The FLOP and byte counts against hand counts, and the peaks table."""

import pytest

from bench import harness, roofline


def test_launch_counts_match_hand_count():
    # The classifier's first layer at the ward launch: 72 sessions x 30
    # chains, one 140-step beat, I=1, H=8, four gates.
    rows, T, i, h = 2160, 140, 1, 8
    # 2 * rows * T * gates * (I + H) * H
    assert roofline.lstm_launch_flops(rows, T, i, h) == 174_182_400
    # x 302,400 + ys 2,419,200 + wx,wh 288 + b 32 + h,c in and out 69,120
    # + rows and lengths 4,320 values, 4 bytes each
    assert roofline.lstm_launch_bytes(rows, T, i, h) == 11_181_440
    t, bound = roofline.least_time(174_182_400, 11_181_440, "TPU v5 lite")
    assert bound == "memory"
    assert t == pytest.approx(11_181_440 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError, match="no peaks"):
        roofline.peaks("cpu")


def test_layer_widths_and_model_flops():
    clf = {"model": "classifier", "cell": "lstm", "input_dim": 1,
           "hidden": 8, "num_layers": 3, "num_classes": 4}
    model = harness.model(clf)
    assert model.layer_widths(clf) == [(1, 8), (8, 8), (8, 8)]
    # per chain-step 2*4*(9*8 + 16*8 + 16*8) = 2624; head 2*8*4 per chunk
    assert model.model_flops(clf, 140, 1) == 140 * 2624 + 64
