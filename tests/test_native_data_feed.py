"""Native MultiSlot data feed: C++ parser vs python fallback parity.

Reference parity: framework/data_feed.cc MultiSlotDataFeed — count-
prefixed float/uint64 slots per line, LoD level-0 offsets.
"""
import numpy as np

from paddle_tpu import native
from paddle_tpu.io.data_feed import MultiSlotDataFeed

DATA = (
    b"2 11 12 1 0.5 3 1.0 2.0 3.0\n"
    b"1 99 1 -0.25 2 4.0 5.0\n"
    b"\n"
    b"3 7 8 9 1 2.5 1 6.0\n"
)
TYPES = "uff"


def test_extension_builds_and_loads():
    assert native.has_native(), "C++ extension failed to build/load"


def test_parse_matches_python_fallback():
    n_c, out_c = native.parse_multislot(DATA, TYPES)
    n_p, out_p = native._parse_multislot_py(DATA, TYPES)
    assert n_c == n_p == 3
    for (vc, lc), (vp, lp) in zip(out_c, out_p):
        np.testing.assert_array_equal(vc, vp)
        np.testing.assert_array_equal(lc, lp)
        assert vc.dtype == vp.dtype


def test_parse_values_and_lod():
    n, out = native.parse_multislot(DATA, TYPES)
    ids, ids_lod = out[0]
    np.testing.assert_array_equal(ids, np.array([11, 12, 99, 7, 8, 9],
                                                np.uint64))
    np.testing.assert_array_equal(ids_lod, [0, 2, 3, 6])
    f1, f1_lod = out[1]
    np.testing.assert_allclose(f1, [0.5, -0.25, 2.5])
    np.testing.assert_array_equal(f1_lod, [0, 1, 2, 3])
    f2, f2_lod = out[2]
    np.testing.assert_allclose(f2, [1.0, 2.0, 3.0, 4.0, 5.0, 6.0])
    np.testing.assert_array_equal(f2_lod, [0, 3, 5, 6])


def test_malformed_input_raises():
    import pytest

    with pytest.raises(ValueError, match="line"):
        native.parse_multislot(b"2 1\n", "u")  # count says 2, one value
    with pytest.raises(ValueError, match="trailing"):
        native.parse_multislot(b"1 5 9\n", "u")  # extra token
    # a short line must NOT steal tokens from the next line
    with pytest.raises(ValueError):
        native.parse_multislot(b"1 5\n1 6 1 7\n", "uu")
    # partial-token consumption: "3.5" must not parse as count 3
    with pytest.raises(ValueError):
        native.parse_multislot(b"3.5 1 2 3\n", "u")
    with pytest.raises(ValueError):
        native.parse_multislot(b"1 2.5\n", "u")  # float token in id slot
    # hex floats, uint64 overflow: rejected by BOTH paths (strtof/strtoull
    # would accept/saturate where python errors — parity means both error)
    for fn in (native.parse_multislot, native._parse_multislot_py):
        with pytest.raises(ValueError):
            fn(b"1 0x10\n", "f")
        with pytest.raises(ValueError):
            fn(b"1 18446744073709551616\n", "u")
    # negative ids wrap into uint64 identically in both paths
    for fn in (native.parse_multislot, native._parse_multislot_py):
        _, out = fn(b"1 -5\n", "u")
        assert int(out[0][0][0]) == 2 ** 64 - 5
    # python fallback raises identically
    with pytest.raises(ValueError, match="line"):
        native._parse_multislot_py(b"2 1\n", "u")
    with pytest.raises(ValueError):
        native._parse_multislot_py(b"3.5 1 2 3\n", "u")
    with pytest.raises(ValueError):
        native._parse_multislot_py(b"1 2.5\n", "u")
    with pytest.raises(ValueError, match="trailing"):
        native._parse_multislot_py(b"1 5 9\n", "u")
    with pytest.raises(ValueError):
        native._parse_multislot_py(b"1 5\n1 6 1 7\n", "uu")


def test_buffer_slice_is_bounded():
    """A memoryview slice must not be read past its logical end."""
    n, out = native.parse_multislot(memoryview(b"1 2 extra")[:4], "u")
    assert n == 1
    np.testing.assert_array_equal(out[0][0], np.array([2], np.uint64))


def test_data_feed_batches(tmp_path):
    # 5 instances, 2 slots: ragged ids + declared-dense float (dim 2);
    # batch_size 2 -> two full batches plus the partial tail batch
    lines = []
    for i in range(5):
        ids = " ".join(str(10 * i + j) for j in range(i + 1))
        lines.append(f"{i + 1} {ids} 2 {i}.0 {i}.5")
    p = tmp_path / "part-0"
    p.write_text("\n".join(lines) + "\n")

    feed = MultiSlotDataFeed([("ids", "u"), ("dense", "f", 2)],
                             batch_size=2)
    batches = list(feed.read_file(str(p)))
    assert len(batches) == 3  # tail batch kept (no silent drop)
    v, lod = batches[0]["dense"]
    assert v.shape == (2, 2)  # declared dim -> deterministic shape
    np.testing.assert_allclose(v, [[0.0, 0.5], [1.0, 1.5]])
    ids_v, ids_lod = batches[1]["ids"]
    np.testing.assert_array_equal(ids_lod, [0, 3, 7])
    np.testing.assert_array_equal(
        ids_v, np.array([20, 21, 22, 30, 31, 32, 33], np.uint64))
    # ragged slot stays flat + lod even when a batch is uniform
    b0_ids, b0_lod = batches[0]["ids"]
    assert b0_ids.ndim == 1
    tail_v, _ = batches[2]["dense"]
    assert tail_v.shape == (1, 2)


def test_native_parse_of_a_large_buffer_is_the_python_feeds():
    """No time is read: what the native path owes a larger buffer is
    the Python feed's parse of it, value for value, offset for offset
    and dtype for dtype, over 2,000 lines of 40-bit ids."""
    rs = np.random.RandomState(0)
    lines = []
    for _ in range(2000):
        n = rs.randint(1, 20)
        ids = " ".join(str(x) for x in rs.randint(0, 1 << 40, n))
        lines.append(f"{n} {ids} 1 {rs.rand():.6f}")
    data = ("\n".join(lines) + "\n").encode()
    assert native.has_native()
    n, out = native.parse_multislot(data, "uf")
    n_py, out_py = native._parse_multislot_py(data, "uf")
    assert n == n_py == 2000
    assert out[1][0].shape == (2000,)
    for (v, lod), (v_py, lod_py) in zip(out, out_py):
        np.testing.assert_array_equal(v, v_py)
        np.testing.assert_array_equal(lod, lod_py)
        assert v.dtype == v_py.dtype
