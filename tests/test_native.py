"""Bit-exact parity of the native (C++) encode pass vs the canonical numpy
paths (device.py fp62, curves/normalize+binnedtime+zorder)."""

import numpy as np
import pytest

from geomesa_tpu import native
from geomesa_tpu.curves.binnedtime import TimePeriod, time_to_binned_time
from geomesa_tpu.curves.sfc import Z2SFC, Z3SFC
from geomesa_tpu.index.device import fp62_lat, fp62_lon

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native toolchain unavailable")


def _corpus(n=50_000, seed=7):
    rng = np.random.default_rng(seed)
    x = rng.uniform(-185, 185, n)  # includes out-of-bounds (lenient clamp)
    y = rng.uniform(-92, 92, n)
    base = np.datetime64("2020-01-01T00:00:00", "ms").astype(np.int64)
    ms = base + rng.integers(0, 400 * 86400000, n)
    # boundary values exercised explicitly
    x[:8] = [-180.0, 180.0, 0.0, -1e-300, 179.99999999999997, -180.1, 180.1, 10.0]
    y[:8] = [-90.0, 90.0, 0.0, 1e-300, 89.99999999999999, -90.1, 90.1, 45.0]
    ms[0] = base
    return x, y, ms


@pytest.mark.parametrize("period", ["day", "week"])
def test_z3_encode_parity(period):
    x, y, ms = _corpus()
    out = native.z3_encode(x, y, ms, period)
    assert out is not None

    xi, xl = fp62_lon(np.clip(x, -180, 180))
    yi, yl = fp62_lat(np.clip(y, -90, 90))
    np.testing.assert_array_equal(out["xi"], xi)
    np.testing.assert_array_equal(out["xl"], xl)
    np.testing.assert_array_equal(out["yi"], yi)
    np.testing.assert_array_equal(out["yl"], yl)

    bins, offs = time_to_binned_time(ms, TimePeriod.parse(period))
    np.testing.assert_array_equal(out["bin16"], bins.astype(np.int16))
    np.testing.assert_array_equal(out["off"], offs.astype(np.int32))
    np.testing.assert_array_equal(out["xf"], x.astype(np.float32))
    np.testing.assert_array_equal(out["yf"], y.astype(np.float32))

    sfc = Z3SFC.apply(TimePeriod.parse(period))
    z = sfc.index(x, y, np.minimum(offs, int(sfc.time.max)), lenient=True)
    np.testing.assert_array_equal(out["z"], z)
    np.testing.assert_array_equal(out["zhi"], (z.astype(np.uint64) >> np.uint64(31)).astype(np.uint32))
    np.testing.assert_array_equal(out["zlo"], (z.astype(np.uint64) & np.uint64(0x7FFFFFFF)).astype(np.uint32))


def test_z2_encode_parity():
    x, y, _ = _corpus(seed=11)
    out = native.z2_encode(x, y)
    assert out is not None
    xi, xl = fp62_lon(np.clip(x, -180, 180))
    yi, yl = fp62_lat(np.clip(y, -90, 90))
    np.testing.assert_array_equal(out["xi"], xi)
    np.testing.assert_array_equal(out["yi"], yi)
    np.testing.assert_array_equal(out["xl"], xl)
    np.testing.assert_array_equal(out["yl"], yl)
    z = Z2SFC().index(x, y, lenient=True)
    np.testing.assert_array_equal(out["z"], z)


def test_fp62_planes_parity():
    x = np.random.default_rng(3).uniform(-180, 180, 10_000)
    got = native.fp62_planes(x, -180.0, 180.0)
    assert got is not None
    hi, lo = fp62_lon(x)
    np.testing.assert_array_equal(got[0], hi)
    np.testing.assert_array_equal(got[1], lo)


def test_month_period_falls_back():
    x, y, ms = _corpus(n=100)
    assert native.z3_encode(x, y, ms, "month") is None


def test_bin_overflow_falls_back():
    """Bins ride as int16 (reference Short bins); epochs past bin 32767 or
    pre-1970 must decline to the numpy path instead of wrapping."""
    x, y, _ = _corpus(n=16)
    x, y = x[:4], y[:4]
    far = np.datetime64("2060-01-01T00:00:00", "ms").astype(np.int64)
    assert native.z3_encode(x[:4], y[:4], np.full(4, far), "day") is None
    assert native.z3_encode(x[:4], y[:4], np.full(4, -1, np.int64), "day") is None
    # week bins reach much further; 2060 is fine there
    assert native.z3_encode(x[:4], y[:4], np.full(4, far), "week") is not None


def test_zranges_parity_with_python_bfs():
    """Native gm_zranges must be bit-identical to the numpy BFS cover
    (same budget rule, same emit, same merge)."""
    import geomesa_tpu.native as N
    from geomesa_tpu import config
    from geomesa_tpu.curves import ranges as R

    rng = np.random.default_rng(7)
    for trial in range(60):
        dims = 2 if trial % 2 else 3
        bits = 31 if dims == 2 else 21
        boxes = []
        for _ in range(int(rng.integers(1, 4))):
            b = []
            for _d in range(dims):
                lo = int(rng.integers(0, (1 << bits) - 1))
                hi = int(rng.integers(lo, min((1 << bits) - 1,
                                              lo + (1 << rng.integers(5, bits)))))
                b.append((lo, hi))
            boxes.append(b)
        mr = int(rng.choice([50, 500, 2000]))
        blo = np.array([[d[0] for d in b] for b in boxes])
        bhi = np.array([[d[1] for d in b] for b in boxes])
        nat = R._zranges_arrays(blo, bhi, bits, mr, 64)
        config.NO_NATIVE.set(True)
        N._lib, N._load_failed = None, False
        try:
            py = R._zranges_arrays(blo, bhi, bits, mr, 64)
        finally:
            config.NO_NATIVE.unset()
            N._lib, N._load_failed = None, False
        for a, b2, name in zip(nat, py, ("lo", "hi", "cont")):
            assert np.array_equal(a, b2), (trial, name)
        # the budget rule really bounds output
        assert len(nat[0]) <= 2 * mr
