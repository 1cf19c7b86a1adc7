"""The reduction from a profiler trace to busy time, kernel time and
launches, and idle gaps named by what the host was doing."""
from pathlib import Path

import pytest

from bench import spec, trace_reduce
from bench.tests.tiny import REPO

HERE = Path(__file__).resolve().parent


def _from_text(path: Path):
    from jax.profiler import ProfileData

    text = "\n".join(l for l in path.read_text().splitlines() if not l.startswith("#"))
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


def test_hand_made_trace_reduces_exactly():
    s = trace_reduce.reduce(_from_text(HERE / "small_trace.pbtxt"), spec.kernels(REPO))
    assert s.window_s == pytest.approx(10e-6)
    assert s.devices == 1
    assert s.busy_s == pytest.approx(3.5e-6)        # [1000, 4000) + [7000, 7500)
    assert s.kernel_launches == {"bitvec_rank": 1}
    assert s.kernel_s["bitvec_rank"] == pytest.approx(3e-6)
    assert s.layer_launches == {"k2-tree descent": 1}
    assert s.requests == 2
    assert s.device_ops == [["fusion.1", pytest.approx(2.5e-6)],
                            ["gather.2", pytest.approx(2e-6)]]
    # gaps [0,1000) under s??; [4000,7000) mostly ?p?; [7500,10000) ?p?
    assert s.idle_gaps == [["bench.request.?p?", pytest.approx(5.5e-6)],
                           ["bench.request.s??", pytest.approx(1e-6)]]


def test_trace_without_device_reports_no_busy_time():
    from jax.profiler import ProfileData

    text = 'planes { id: 2 name: "/host:CPU" }'
    prof = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    s = trace_reduce.reduce(prof, spec.kernels(REPO))
    assert s.devices == 0 and s.busy_s == 0.0 and s.kernel_launches == {}


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def test_recorded_chip_trace_reduces_to_its_counts():
    """A slice of a jamendo.lookup trace recorded on a v5e: three requests,
    the descent's rank programs matched by the bitvec_rank kernel file."""
    s = trace_reduce.reduce(_from_text(HERE / "recorded_lookup_trace.pbtxt"),
                            spec.kernels(REPO))
    assert s.devices == 1 and s.requests == 3
    assert s.window_s == pytest.approx(0.487424124)
    assert s.busy_s == pytest.approx(0.028693795)
    assert s.kernel_launches == {"bitvec_rank": 257}
    assert s.layer_s["k2-tree descent"] == pytest.approx(0.028794272)
    assert s.busy_s < s.window_s
    assert s.device_ops[0] == ["%fusion = s32[32768] fusion", pytest.approx(0.005144925)]
    assert s.idle_gaps[0] == ["bench.request.sp?", pytest.approx(0.264406498)]
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(s.window_s - s.busy_s)


def test_short_op_names():
    f = trace_reduce.short_op_name
    assert f("%fusion.1 = u32[65536]{0:T(1024)S(1)} fusion(u32[1048576]{0:T(1024)S(1)} "
             "%copy-done), kind=kCustom") == "%fusion.1 = u32[65536] fusion"
    assert f("%copy-start = (u32[8]{0:T(1024)S(1)}, u32[]{:S(2)}) copy-start(u32[8]{0} %w)") \
        == "%copy-start = (u32[8], u32[]) copy-start"
    assert f("fusion.1") == "fusion.1"
