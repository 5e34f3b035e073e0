"""The reduction from a trace to numbers, on a hand-made event list
with known answers, and the splash roofline count against arithmetic
done by hand. (The real traces of PR 24 are tens of MB; the layout they
showed is described at the top of harness/trace_reduce.py.)"""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from harness import roofline, stats, trace_reduce  # noqa: E402

MS = 1_000_000


def synthetic():
    """One chip, 100 ms from the first op to the end of the last.

        0-10   fusion.1
        10-30  while.2, holding  12-20 paged_ragged.3
                                 22-28 fusion.1 again
        30-50  idle; the host was in `client_idle` for 32-48
        50-60  paged_ragged.3
        60-65  idle, no annotation
        65-100 copy.7
    """
    ops = [("%fusion.1 = bf16[64,2048]{1,0} fusion(bf16[8] %p.1)", 0,
            10 * MS),
           ("%while.2 = (s32[], bf16[8]) while(%tuple.1)", 10 * MS,
            20 * MS),
           ("paged_ragged.3 = bf16[64,1,16,128]{3,2,1,0} custom-call()",
            12 * MS, 8 * MS),
           ("%fusion.1 = bf16[64,2048]{1,0} fusion(bf16[8] %p.1)",
            22 * MS, 6 * MS),
           ("paged_ragged.3 = bf16[64,1,16,128]{3,2,1,0} custom-call()",
            50 * MS, 10 * MS),
           ("%copy.7 = bf16[8]{0} copy(%fusion.1)", 65 * MS, 35 * MS)]
    modules = [("jit_serving_mixed_step(123)", 0, 30 * MS),
               ("jit_serving_mixed_step(123)", 50 * MS, 50 * MS)]
    host = [("client_idle", 32 * MS, 16 * MS),
            ("something_else", 60 * MS, 5 * MS)]
    return {"/device:TPU:0": {"ops": ops, "modules": modules}}, host


def test_busy_idle_names_gaps():
    device, host = synthetic()
    r = trace_reduce.reduce_events(device, host, labels=("client_idle",))
    assert r.chips == 1
    assert r.window_s == pytest.approx(0.100)
    assert r.busy_s == pytest.approx(0.075)
    assert r.idle_share == pytest.approx(0.25)
    # self time: the while keeps 20 - 8 - 6 = 6 ms
    assert r.ops["while.2"] == [pytest.approx(0.006), 1]
    assert r.ops["fusion.1"] == [pytest.approx(0.016), 2]
    assert r.ops["paged_ragged.3"] == [pytest.approx(0.018), 2]
    assert r.ops["copy.7"] == [pytest.approx(0.035), 1]
    assert sum(v[0] for v in r.ops.values()) == pytest.approx(r.busy_s)
    assert r.seconds_of("paged_ragged") == pytest.approx(0.018)
    assert r.calls_of("serving_mixed_step", "modules") == 2
    assert r.modules["jit_serving_mixed_step"] == [pytest.approx(0.08), 2]
    # longest gap first, each named by what the host was doing
    assert [(round(d, 6), n) for _, d, n in r.gaps] == [
        (0.020, "client_idle"), (0.005, trace_reduce.DEFAULT_GAP)]
    b = r.breakdown()
    assert b["device_ops"][0] == ["copy.7", pytest.approx(0.035)]
    assert b["idle_gaps"] == [["client_idle", pytest.approx(0.020)],
                              [trace_reduce.DEFAULT_GAP,
                               pytest.approx(0.005)]]
    assert len(b["device_ops"]) <= 10


def test_two_chips_average():
    device, host = synthetic()
    device["/device:TPU:1"] = {"ops": [("%fusion.9 = f32[] x()", 0,
                                        50 * MS)],
                               "modules": []}
    r = trace_reduce.reduce_events(device, host)
    assert r.chips == 2
    assert r.busy_s == pytest.approx((0.075 + 0.050) / 2)
    assert r.window_s == pytest.approx((0.100 + 0.050) / 2)
    assert r.seconds_of("paged_ragged") == pytest.approx(0.009)


def test_splash_roofline_by_hand():
    # B 32, H 16, S 1024, D 64, causal, bf16, forward + backward
    flops, nbytes = roofline.splash_mha_fwd_bwd(32, 16, 1024, 64)
    matmul = 2 * 32 * 16 * 1024 * 1024 * 64        # 68.7 GFLOP, full
    assert flops == 6 * matmul / 2 == 206158430208
    n = 32 * 16 * 1024 * 64                        # 33.5 M elements
    lse = 32 * 16 * 1024 * 4
    assert nbytes == (4 + 8) * n * 2 + 2 * lse == 809500672
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # 1.0465 ms of FLOPs against 0.9884 ms of bytes: compute-bound
    share, bound = roofline.roofline(flops, nbytes, 2.093e-3, peaks)
    assert bound == "compute"
    assert share == pytest.approx(50.0, abs=0.01)
    share, bound = roofline.roofline(1.0, nbytes, 1.0, peaks)
    assert bound == "bandwidth"


def test_percentile_and_spread():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 90) == 90
    assert stats.percentile([1.0, 2.0], 50) == 1.5
    assert stats.percentile([1.0, float("inf")], 90) == float("inf")
    assert stats.percentile([], 90) is None
    assert stats.spread([10, 10, 10, 10, 10, 10]) == 0
    # 350M at seq 1024: 2.28 GFLOP a token (ISSUE 24)
    f = stats.gpt_train_flops_per_token(1024, 24, 1024, 50304)
    assert f == 6 * (12 * 24 * 1024 ** 2 + 50304 * 1024 + 1024 * 1024) \
        + 6 * 24 * 1024 * 1024
    assert 2.27e9 < f < 2.29e9
    with pytest.raises(KeyError):
        stats.load_peaks("no such chip")
    assert stats.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
