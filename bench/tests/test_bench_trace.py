"""The trace reduction and the roofline arithmetic, on synthetic events."""
import pytest

import roofline
import tracereduce as tr


def _ev(name, start, dur, device="/device:TPU:0", **stats):
    return tr.DeviceEvent(device, name, float(start), float(dur),
                          tuple((k, str(v)) for k, v in stats.items()))


def _trace():
    # window 1 s; ops (ns): [0, 100), [50, 150) overlap, [400, 500),
    # the kernel at [1000, 1300) and [5000, 5200); host spans around them
    events = [_ev("fusion.1", 0, 100), _ev("fusion.2", 50, 100),
              _ev("copy.3", 400, 100),
              _ev("custom-call.7", 1000, 300, hlo_op="_score_kernel"),
              _ev("_score_kernel", 5000, 200)]
    spans = [tr.HostSpan("bench.pack", 120, 1000),
             tr.HostSpan("bench.settle", 1300, 4000)]
    return tr.reduce(1e-6 * 10, events, spans)


def test_union_counts_overlap_once():
    assert tr.union_ns([(0, 100), (50, 150), (400, 500)]) == 250
    assert tr.union_ns([(0, 10), (0, 10)]) == 10
    assert tr.union_ns([]) == 0


def test_busy_idle_and_kernel_time():
    red = _trace()
    busy_ns = 150 + 100 + 300 + 200
    assert red.busy_s == pytest.approx(busy_ns / 1e9)
    assert red.idle_share == pytest.approx(1 - busy_ns / 1e9 / 1e-5)
    kernel = [e for e in red.events if e.matches("_score_kernel")]
    assert sum(e.dur_ns for e in kernel) == 500


def test_gaps_are_attributed_to_the_open_span():
    red = _trace()
    assert tr.gaps_ns([(0, 100), (50, 150), (400, 500)]) == [(150, 400)]
    by = red.idle_by_span()
    # gaps: (150,400) mid 275 in pack; (500,1000) mid 750 in pack;
    # (1300,5000) mid 3150 in settle
    assert by["pack"] == pytest.approx((250 + 500) / 1e9)
    assert by["settle"] == pytest.approx(3700 / 1e9)
    bd = red.breakdown()
    assert bd["idle_gaps"][0] == ["settle", pytest.approx(3700 / 1e9)]
    assert bd["device_ops"][0][0] in ("custom-call.7", "_score_kernel")
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_busy_is_averaged_over_devices():
    ev = [_ev("a", 0, 100, device="/device:TPU:0"),
          _ev("b", 0, 300, device="/device:TPU:1")]
    assert tr.reduce(1.0, ev, []).busy_s == pytest.approx(200 / 1e9)


def test_empty_window_is_an_error():
    with pytest.raises(tr.TraceError):
        tr.reduce(0.0, [], [])


def test_score_cost_matches_a_hand_count():
    # m = 256 rows, one job feature, four system features, one grid point:
    # words read = 256*1 + 256*4 + 1 + 4 + 2*256*1 + 3*256 = 2565,
    # written = 2*256 = 512  -> 3077 words of 4 bytes
    ops, nbytes = roofline.score_cost(256, 1, 4, 1)
    assert nbytes == 4 * 3077
    # 2*256*5 + 3*256 + 4*256 + 256 = 2560 + 768 + 1024 + 256
    assert ops == 4608


def test_least_time_is_memory_bound_on_v5e():
    peaks = roofline.peaks_for("TPU v5 lite")
    ops, nbytes = roofline.score_cost(1 << 16, 1, 4, 1)
    t, side = roofline.least_seconds(ops, nbytes, peaks)
    assert side == "memory"
    assert t == pytest.approx(nbytes / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(roofline.UnknownDevice):
        roofline.peaks_for("TPU v9 imaginary")


KERNEL_EVENT = (
    "%score_variants_pallas.1 = (f32[1,32768]{1,0:T(1,128)}, "
    "s32[1,32768]{1,0:T(1,128)}) custom-call(f32[32768,1]{1,0:T(8,128)S(1)} "
    "%copy.4, f32[32768,4]{1,0:T(8,128)S(1)} %copy.5, f32[1,1]{1,0:T(1,128)} "
    "%bitcast.6, f32[1,4]{1,0:T(1,128)} %bitcast.11, "
    "f32[32768,1]{1,0:T(8,128)S(1)} %copy.6, f32[32768,1]{1,0:T(8,128)S(1)} "
    "%copy.7, f32[32768,1]{1,0:T(8,128)S(1)} %copy.8, "
    "f32[32768,1]{1,0:T(8,128)S(1)} %copy.9, f32[32768,1]{1,0:T(8,128)S(1)} "
    "%copy.10), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={f32[32768,1]{1,0}}")


def test_kernel_shapes_are_read_from_the_hlo_text():
    # an event as a v5e trace names it (one round of the pod cell)
    shapes = roofline.custom_call_shapes(KERNEL_EVENT)
    assert shapes[:6] == [(32768, 1), (32768, 4), (1, 1), (1, 4),
                          (32768, 1), (32768, 1)]
    ev = _ev(KERNEL_EVENT, 0, 170886)
    assert roofline.is_score_kernel(ev)
    assert not roofline.is_score_kernel(_ev(
        "%copy.4 = f32[32768,1]{1,0:T(8,128)S(1)} copy(f32[32768,1] %x)", 0, 1))


def test_roofline_reader_on_a_recorded_kernel_event():
    import harness

    red = tr.reduce(1.0, [_ev(KERNEL_EVENT, 0, 170886)], [])

    class Run:
        rounds = 1

    ctx = harness.Context(run=Run(), device={}, trace=red,
                          peaks=roofline.peaks_for("TPU v5 lite"))
    share = harness.metric_reader("score_kernel_roofline")(ctx)
    _, nbytes = roofline.score_cost(32768, 1, 4, 1)
    assert share == pytest.approx(100 * nbytes / 819e9 / 170886e-9)
    assert 0 < share < 100
    assert harness.metric_reader("score_kernel_ms")(ctx) == \
        pytest.approx(0.170886)
