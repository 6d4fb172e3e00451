"""The roofline byte count and the peak table."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

from bench import roofline  # noqa: E402


def test_get_bytes_on_a_hand_worked_call():
    # 64 live GETs, 60 hits, H=8, V=4: each reads its key and 8 key words
    # and writes a found word and 4 value words (14 words); each hit also
    # reads 4 value words.  64 * 14 + 60 * 4 = 1136 words = 4544 bytes.
    assert roofline.get_bytes(64, 60, 8, 4) == 4544
    assert roofline.get_bytes(0, 0, 8, 4) == 0
    assert roofline.get_bytes(1, 0, 8, 7) == 4 * (1 + 8 + 8)


def test_peak_table_knows_the_v5e_and_refuses_the_rest():
    row = roofline.peak("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert "cloud.google.com" in row["source"]
    with pytest.raises(KeyError):
        roofline.peak("TPU v4")
