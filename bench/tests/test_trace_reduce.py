"""The trace reduction on synthetic event lists with known totals."""
import pytest

import trace_reduce as tr

MS = 1e6  # ns


def _ops(device, rows):
    return tr.device_ops(device, [(text, s * MS, e * MS) for text, s, e in rows])


def test_classify_reads_the_opcode():
    assert tr.classify("%fitness.12 = f32[104,1]{1,0} custom-call(s32[1,63] %a)") \
        == ("fitness.12", "mosaic")
    assert tr.classify("%all-gather.3 = f32[4,100]{1,0} all-gather(f32[1,100] %p)")[1] \
        == "collective"
    assert tr.classify("%cp = (u32[2], s32[]) collective-permute-start(u32[2] %k)")[1] \
        == "collective"
    assert tr.classify("%fusion.808 = s32[6300]{0:T(1024)S(1)} fusion(s32[100,63] %x)") \
        == ("fusion.808", "other")
    assert tr.classify("%while.14 = (s32[]{:T(128)}, u32[2]) while((s32[], u32[2]) %t)")[1] \
        == "other"


def test_busy_idle_kernels_and_nesting():
    dev = "/device:TPU:0"
    ops = _ops(dev, [
        ("%while.1 = (s32[]) while((s32[]) %t)", 10, 60),  # encloses the next three
        ("%fitness.2 = f32[8,1] custom-call(s32[8,63] %o)", 10, 40),
        ("%fusion.3 = s32[6300] fusion(s32[100,63] %o)", 40, 50),
        ("%fitness.4 = f32[8,1] custom-call(s32[8,63] %o)", 50, 60),
        ("%fusion.5 = s32[6300] fusion(s32[100,63] %o)", 80, 90),
    ])
    assert [o.leaf for o in ops] == [False, True, True, True, True]
    spans = [("bench.traced", 0, 100 * MS), ("fit.init", 0, 10 * MS),
             ("fit.evolve", 10 * MS, 75 * MS), ("fit.init", 75 * MS, 100 * MS)]
    red = tr.reduce(ops, spans, devices=[dev])
    assert red.window_s == pytest.approx(0.1)
    assert red.busy_s[dev] == pytest.approx(0.060)  # 10-60 and 80-90
    assert red.kind_mean_s("mosaic") == pytest.approx(0.040)
    assert red.kind_mean_s("other") == pytest.approx(0.020)  # leaves only
    assert red.kind_mean_s("collective") == 0.0
    assert dict((n, t) for n, t in red.top_ops)["fitness.2"] == pytest.approx(0.030)
    assert "while.1" not in dict(red.top_ops)
    gaps = dict(red.idle_gaps)  # idle: 0-10 init, 60-75 evolve, 75-80 and 90-100 init
    assert gaps["fit.init"] == pytest.approx(0.025)
    assert gaps["fit.evolve"] == pytest.approx(0.015)


def test_window_clips_and_idle_devices_count():
    a, b = "/device:TPU:0", "/device:TPU:1"
    ops = _ops(a, [("%f.1 = f32[1] fusion(f32[1] %x)", 0, 50)])
    red = tr.reduce(ops, [], window=(20 * MS, 40 * MS), devices=[a, b])
    assert red.busy_s == {a: pytest.approx(0.020), b: 0.0}
    assert red.mean(red.busy_s) == pytest.approx(0.010)


def test_exposed_collective_time():
    dev = "/device:TPU:0"
    ops = _ops(dev, [
        ("%fitness.1 = f32[8,1] custom-call(s32[8,63] %o)", 0, 30),
        ("%all-gather.2 = f32[4,100] all-gather(f32[1,100] %p)", 20, 50),
        ("%fusion.3 = f32[4] fusion(f32[4,100] %g)", 40, 45),
    ])
    red = tr.reduce(ops, [], window=(0, 100 * MS), devices=[dev])
    # the collective runs 20-50; compute covers 20-30 and 40-45
    assert red.exposed_collective_s[dev] == pytest.approx(0.015)
    assert red.kind_mean_s("collective") == pytest.approx(0.030)


def test_interval_helpers():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_missing_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce([], [("fit.init", 0, 1)])
