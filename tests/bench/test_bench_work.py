"""The acq_score work count against a hand count at a small shape."""

import pytest

from bench.work import acq_score as work


def test_hand_count_lower_triangle():
    # S=2 draws, m=3 anchors, n=4 rows, d=2, one head.
    # per anchor: gram 4*(3*2+8)=56, mean 2*4=8, lower-triangle solve
    # 4*5=20 (10 multiply-adds), square-and-sum 8, closed form 20 -> 112
    assert work.flops(2, 3, 4, 2) == 2 * 3 * 112
    # per draw: anchors 3*2 + rows 4*2 + triangle 4*5/2 + alpha 4 + amp 1
    # = 29; plus the mask (4) once and the scores (2*3), in float32
    assert work.bytes_moved(2, 3, 4, 2) == 4 * (2 * 29 + 4 + 6)


def test_least_time_is_the_larger_bound():
    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    call = (10, 1024, 512, 11, 1)
    t = work.least_seconds(call, peaks)
    assert t == max(work.flops(*call) / 197e12, work.bytes_moved(*call) / 819e9)
    # the paper cell's anchor sweep is bound by compute on a v5e
    assert work.flops(*call) / 197e12 > work.bytes_moved(*call) / 819e9


def test_calls_of_one_acquisition():
    shape = {"s": 10, "n": 512, "d": 11, "num_anchors": 1024, "num_refine": 8}
    assert work.calls(shape) == [(10, 1024, 512, 11, 1), (10, 8, 512, 11, 1)]


def test_roofline_pairs_kernel_events_with_their_call():
    import types

    from bench.metrics import acq_score_roofline

    peaks = {"flops_per_s": 197e12, "bytes_per_s": 819e9}
    label = "bench.acq_opt s=10 n=512 d=11 a=1024 r=8"
    trace = {
        "chips_traced": 1,
        # three calls; the last has no successor inside the window
        "annotations": [(label, 0, 10), (label, 100_000, 100_010),
                        (label, 200_000, 200_010)],
        # the second call has one of its two events only
        "kernel_events": {"acq_score": [(1_000, 40_000), (50_000, 2_000),
                                        (101_000, 40_000), (201_000, 40_000),
                                        (250_000, 2_000)]},
    }
    run = types.SimpleNamespace(trace=trace, peaks=peaks)
    least = sum(work.least_seconds(c, peaks) for c in work.calls(
        {"s": 10, "n": 512, "d": 11, "num_anchors": 1024, "num_refine": 8}))
    # only the first call counts: 42 us of device time
    assert acq_score_roofline.read(run) == pytest.approx(100.0 * least / 42e-6)
