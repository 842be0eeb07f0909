"""The trace reduction on a hand-built trace: busy union, idle share,
kernel matching, the breakdown, and the readers."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from chipbench_cells import BENCH, harness  # noqa: F401  (sets sys.path)
from chipbench import trace as tr

ADAM = ('%fused_adam.12 = (f32[50176,128]{1,0}) custom-call(f32[2,128]{1,0} '
        '%b, f32[50176,128]{1,0} %p), custom_call_target="tpu_custom_call"')
GATHER = ('%closed_call.20 = f32[32,3136]{1,0} custom-call(s32[32]{0:T(128)} '
          '%idx, f32[5696,3136]{1,0:T(8,128)} %copy.512, '
          'f32[5696,3136]{1,0} %copy.512), '
          'custom_call_target="tpu_custom_call"')
FUSION = '%fusion.7 = f32[32,2048]{1,0} fusion(f32[32,3136]{1,0} %a), kind=kOutput'
ALLRED = '%all-reduce.3 = f32[2048]{0} all-reduce(f32[2048]{0} %g), replica_groups={}'


def ev(name, s, e):
    return tr.Event(name, float(s), float(e))


@pytest.fixture
def trace():
    # window [100, 200] ns; device 0 busy on [90,120] (clipped to 100),
    # [110,130] (overlaps), [150,160], [195,250] (clipped to 200)
    d0 = [ev(ADAM, 90, 120), ev(GATHER, 110, 130), ev(FUSION, 150, 160),
          ev(ALLRED, 195, 250)]
    d1 = [ev(ADAM, 100, 110), ev(ALLRED, 120, 170)]
    host = [ev("sync", 128, 152), ev("sample", 160, 190)]
    return tr.Trace({"/device:TPU:0": d0, "/device:TPU:1": d1}, host,
                    (100.0, 200.0))


def test_union_merges_overlaps_and_clips():
    got = tr.union([ev("a", 90, 120), ev("b", 110, 130), ev("c", 150, 160)],
                   100, 200)
    assert got == [[100, 130], [150, 160]]


def test_busy_and_idle(trace):
    busy = tr.busy_ns(trace)
    assert busy["/device:TPU:0"] == 30 + 10 + 5
    assert busy["/device:TPU:1"] == 10 + 50
    reader = harness.load_module(BENCH / "metrics" / "device.idle_pct.py", "i")
    idle = reader.read(SimpleNamespace(trace=trace))
    assert idle == pytest.approx(100 * (1 - (45 + 60) / 2 / 100))


def test_kernel_matchers(trace):
    adam = harness.load_module(BENCH / "metrics" / "fused_adam_roofline.py",
                               "a").MATCH
    gather = harness.load_module(
        BENCH / "metrics" / "feature_resample_roofline.py", "g").MATCH
    names = [ADAM, GATHER, FUSION, ALLRED]
    assert [adam(ev(n, 0, 1)) for n in names] == [True, False, False, False]
    assert [gather(ev(n, 0, 1)) for n in names] == [False, True, False, False]
    # only ops wholly inside the window count towards an op's time
    assert tr.op_ns(trace, adam) == {"/device:TPU:0": 0,
                                     "/device:TPU:1": 10}


def test_roofline_share_and_silence(trace):
    ctx = SimpleNamespace(trace=trace, traced_rounds=1,
                          peak={"hbm_bytes_per_s": 1e9})
    adam = tr.kernel(("fused_adam",))
    # 10 ns of kernel on device 1, 0 on device 0: mean 5 ns; 4 bytes at
    # 1 GB/s take 4 ns
    assert tr.roofline_pct(ctx, adam, 4.0) == pytest.approx(80.0)
    assert tr.roofline_pct(ctx, tr.kernel(("no_such_kernel",)), 4.0) is None


def test_breakdown(trace):
    top = tr.top_ops(trace)
    assert top[0][0] == "closed_call.20 custom-call"
    assert top[0][1] == pytest.approx(20e-9)
    gaps = tr.idle_gaps(trace)
    # device 0 idles on [130, 150] (host in sync) and [160, 195] (sample)
    assert gaps[0][0] == "sample" and gaps[0][1] == pytest.approx(35e-9)
    assert gaps[1][0] == "sync" and gaps[1][1] == pytest.approx(20e-9)


def test_short_names():
    assert tr.short(ADAM) == "fused_adam.12 custom-call"
    assert tr.short(FUSION) == "fusion.7 fusion"
    assert tr.short("%while.3 = (s32[], f32[2]{0}) while((s32[]) %t)") == \
        "while.3 while"
