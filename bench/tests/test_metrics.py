"""Each per-layer reader on fixed inputs, and a reader with nothing to
read returns nothing."""
import pytest

import trace_reduce as tr
from harness.cells import metric_reader

DEV = "/device:TPU:0"
MS = 1e6


@pytest.fixture
def trace():
    rows = [("%fitness.1 = f32[8,1] custom-call(s32[8,63] %o)", 0, 30),
            ("%fusion.2 = f32[8] fusion(f32[8] %f)", 30, 40),
            ("%all-gather.3 = f32[4,8] all-gather(f32[1,8] %f)", 40, 50)]
    ops = tr.device_ops(DEV, [(t, s * MS, e * MS) for t, s, e in rows])
    return tr.reduce(ops, [], window=(0, 100 * MS), devices=[DEV])


PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}


def ctx(trace, **kw):
    base = {"trace": trace, "peaks": PEAKS, "chips": 1, "counters": {},
            "spans": {}, "work": {"traced_generations": 10,
                                  "node_row_apps": 6e9, "data_bytes": 3e6}}
    base.update(kw)
    return base


def test_fit_readers(trace):
    c = ctx(trace)
    assert metric_reader("device_idle_pct.fit")(c) == pytest.approx(50.0)
    assert metric_reader("eval_kernel_ms.fit")(c) == pytest.approx(3.0)
    assert metric_reader("step_other_ms.fit")(c) == pytest.approx(2.0)
    # least time: max(6e9 / 1e12, 3e6 / 1e9) = 6 ms over 30 ms of kernels
    assert metric_reader("eval_roofline")(c) == pytest.approx(20.0)


@pytest.mark.parametrize("name", [
    "device_idle_pct.fit", "eval_kernel_ms.fit", "eval_roofline",
    "step_other_ms.fit"])
def test_nothing_to_read_returns_nothing(name):
    empty = tr.reduce([], [], window=(0, 100 * MS), devices=[DEV])
    assert metric_reader(name)(ctx(empty, spans={})) is None
    assert metric_reader(name)(ctx(None, spans={})) is None
