"""The reduction from an .xplane.pb, against the small trace recorded on
one v5e chip (three rounds of: the three flash kernels at
[2,32,1024,128], one bf16 matmul, a fetch under a benchmark span)."""

import os

import pytest

from benchmark import trace

from benchmark import harness as hs

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")
SIZES = {"rows": 2, "heads": 32, "kv_heads": 8, "seq": 1024, "head_dim": 128}


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce_file(SMALL, None, SIZES)


def test_interval_arithmetic():
    assert trace.union_length([(0, 2), (1, 3), (5, 6)]) == 4
    assert trace.merged([(1, 3), (0, 2), (5, 6)]) == [(0, 3), (5, 6)]
    # collectives [0,4] and [6,8], compute covers [1,3] and [7,9]
    assert trace.minus([(0, 4), (6, 8)], [(1, 3), (7, 9)]) == 3


def test_busy_time_and_window(reduced):
    assert reduced["devices"] == 1
    assert reduced["busy_s"] == pytest.approx(9.653e-3, rel=1e-3)
    assert reduced["busy_s"] < reduced["window_s"]


def test_flash_kernels_are_told_apart(reduced):
    assert reduced["kernel_count"] == {"flash_fwd": 3, "flash_dq": 3,
                                       "flash_dkv": 3}
    t = reduced["kernel_time"]
    assert t["flash_fwd"] == pytest.approx(1.7486e-3, rel=1e-3)
    assert t["flash_dq"] == pytest.approx(1.2519e-3, rel=1e-3)
    assert t["flash_dkv"] == pytest.approx(1.9173e-3, rel=1e-3)


@pytest.mark.parametrize("sizes", [
    dict(SIZES, seq=2048),                               # other shapes
    {k: v for k, v in SIZES.items() if k != "kv_heads"},  # a size unknown
    {}])
def test_a_kernel_that_matches_no_file_is_an_error(sizes):
    with pytest.raises(hs.BenchFailure, match="benchmark/kernels"):
        trace.reduce_file(SMALL, None, sizes)


def test_breakdown_names_ops_and_gaps(reduced):
    ops = reduced["breakdown"]["device_ops"]
    assert len(ops) <= 10 and ops[0][0] == "%fusion fusion bf16[2048,14336]"
    assert any("[flash_dkv]" in name for name, _ in ops)
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    # the device waited while the host slept inside the fetch span
    assert max(gaps, key=gaps.get) == "fetch_metrics"
    assert gaps["fetch_metrics"] == pytest.approx(0.02344, rel=1e-2)


def test_short_name():
    assert trace.short_name(
        "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %x), kind=kLoop"
    ) == "%fusion.3 fusion bf16[8]"
    assert trace.op_of("%while.2 = (s32[]{:T(128)}, bf16[4]{0}) while((s32[], "
                       "bf16[4]{0}) %tuple), condition=%c, body=%b") == "while"
