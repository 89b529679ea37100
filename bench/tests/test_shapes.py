"""Bytes a search must move, from shapes; the table of peaks."""

import json
import os

import pytest

import shapes as S

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_hand_worked_rows():
    # 10 generated rows of 2 state words + 4 key words: 24 B a row, written
    # and read once = 480 B; 4 distinct keys of 16 B once = 64 B
    assert S.search_bytes(10, 4, 2, 4) == 544
    # exact keys on the packed state: 2 + 2 words
    assert S.search_bytes(10, 4, 2, 2) == 2 * 10 * 16 + 4 * 8
    assert S.search_bytes(0, 0, 2, 4) == 0


def test_the_cells_sizes():
    # desk-recheck-4p8: 4,767,576 x 24 B x 2 + 1,859,252 x 16 B
    assert S.search_bytes(4767576, 1859252, 2, 4) == 258591680
    assert S.roofline_share(819e9, 1.0, 819e9) == pytest.approx(100.0)
    assert S.roofline_share(258591680, 12.0, 819e9) == \
        pytest.approx(0.00263, rel=0.01)


def test_unknown_device_kind_is_an_error():
    peaks = json.load(open(os.path.join(BENCH, "peaks.json")))
    assert S.peak_for("TPU v5 lite", peaks)["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        S.peak_for("TPU v9 imaginary", peaks)
    with pytest.raises(KeyError):
        S.peak_for("_source", peaks)
