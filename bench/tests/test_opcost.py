import numpy as np

from bench import opcost


def test_window_call_bytes():
    shape = opcost.call_shape(
        "segment_window_agg",
        (None, None, None, np.array([0, 100, 250]), (0, 0, 1, 1)), {})
    assert shape == {"rows": 250, "segments": 2, "gx": 1, "gy": 1,
                     "window": True}
    # 250 objects * 12 B + 2 segments * 4 window corners * 4 B
    # + 2 segments * 1 cell * (count, sum, min, max) * 4 B
    assert opcost.bytes_needed(shape) == \
        3000 + 32 + 32


def test_split_call_bytes():
    edges_x = np.zeros((3, 4))   # 3 segments, 3 columns each
    edges_y = np.zeros((3, 3))   # 2 rows each
    shape = opcost.call_shape(
        "segment_bin_agg_edges",
        (None, None, None, np.array([0, 10, 20, 40]), edges_x, edges_y), {})
    assert shape == {"rows": 40, "segments": 3, "gx": 3, "gy": 2,
                     "window": False}
    # thresholds: (3 - 1) + (2 - 1) per segment; 6 cells per segment
    assert opcost.bytes_needed(shape) == \
        40 * 12 + 3 * 3 * 4 + 3 * 6 * 16


def test_fused_select_bytes():
    shape = opcost.call_shape(
        "segment_window_bin_select_multi",
        (None, None, None, np.array([0, 1000]), None, None, None),
        {"bx": 8, "by": 8})
    assert shape["gx"] == 8 and shape["gy"] == 8 and shape["window"]
    # objects + window corners and 7 + 7 thresholds + 64 cells of 4
    # numbers; the suffix epilogue is not the kernel's
    assert opcost.bytes_needed(shape) == 12000 + (4 + 14) * 4 + 64 * 16


def test_single_segment_ops():
    xs = np.zeros(500, np.float32)
    assert opcost.call_shape("window_agg", (xs, xs, xs, None), {})["rows"] \
        == 500
    s = opcost.call_shape("bin_agg", (xs, xs, xs, None),
                          {"gx": 2, "gy": 2, "n": 300})
    assert s == {"rows": 300, "segments": 1, "gx": 2, "gy": 2,
                 "window": False}
    assert opcost.call_shape("window_mask_np", (), {}) is None
