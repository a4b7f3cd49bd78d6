"""The session host loop's readers on a synthetic trace whose idle gaps
under each program span are known, and on traces without program spans.

Timeline (ms) of one fit in a 100 ms window, with the harness's spans
`fit.init` and `fit.evolve` around the program's own:

    fit.init 0-20      fit.init_state 2-18
                         fit.init_population 4-10, fit.init_restore 14-17
    fit.evolve 20-95   fit.block 21-86: fit.dispatch 21-25, fit.sync 25-85
                       fit.absorb 86-91
    device ops         5-6, 8-9, 12-13 (init), 24-80 (the block)

Idle time by innermost open span: fit.init 4 (0-2, 18-20),
fit.init_state 6, fit.init_population 4, fit.init_restore 3,
fit.evolve 5 (20-21, 91-95), fit.dispatch 3, fit.sync 5, fit.block 1,
fit.absorb 5, outside spans 5 (95-100).
"""
import pytest

import trace_reduce as tr
from harness.cells import metric_reader

DEV = "/device:TPU:0"
MS = 1e6
GENS = 10

HARNESS = [("bench.traced", 0, 100), ("fit.init", 0, 20),
           ("fit.evolve", 20, 95)]
PROGRAM = [("fit.init_state", 2, 18), ("fit.init_population", 4, 10),
           ("fit.init_restore", 14, 17), ("fit.block", 21, 86),
           ("fit.dispatch", 21, 25), ("fit.sync", 25, 85),
           ("fit.absorb", 86, 91)]
OPS = [("%fusion.1 = u32[2] fusion(u32[2] %k)", 5, 6),
       ("%fusion.2 = s32[100,63] fusion(u32[2] %k)", 8, 9),
       ("%broadcast.3 = f32[100] broadcast(f32[] %c)", 12, 13),
       ("%gp_tree_eval.4 = f32[104,1] custom-call(s32[104,63] %o)", 24, 70),
       ("%fusion.5 = s32[100,63] fusion(s32[100,63] %o)", 70, 80)]


def _reduce(spans):
    ops = tr.device_ops(DEV, [(t, s * MS, e * MS) for t, s, e in OPS])
    return tr.reduce(ops, [(n, s * MS, e * MS) for n, s, e in spans],
                     devices=[DEV])


def _ctx(trace):
    return {"trace": trace, "peaks": {}, "chips": 1, "counters": {},
            "spans": {}, "work": {"traced_generations": GENS}}


@pytest.fixture
def traced():
    return _reduce(HARNESS + PROGRAM)


def test_idle_gaps_land_on_program_spans(traced):
    gaps = dict(traced.idle_gaps)
    assert gaps == pytest.approx({
        "fit.init": 0.004, "fit.init_state": 0.006,
        "fit.init_population": 0.004, "fit.init_restore": 0.003,
        "fit.evolve": 0.005, "fit.dispatch": 0.003, "fit.sync": 0.005,
        "fit.block": 0.001, "fit.absorb": 0.005, "outside spans": 0.005})


@pytest.mark.parametrize("name, idle_ms", [
    ("init_idle_ms.fit", 6 + 4 + 3),
    ("boundary_idle_ms.fit", 3 + 5 + 1 + 5)])
def test_reader_sums_its_spans(traced, name, idle_ms):
    assert metric_reader(name)(_ctx(traced)) == pytest.approx(idle_ms / GENS)


def test_budget_closes_but_for_the_harness_spans(traced):
    """Kernel time, other busy time and both idle readings add up to the
    window per generation, less the idle left under the harness's spans
    and outside them."""
    c = _ctx(traced)
    total = sum(metric_reader(n)(c) for n in (
        "eval_kernel_ms.fit", "step_other_ms.fit", "init_idle_ms.fit",
        "boundary_idle_ms.fit"))
    left = 4 + 5 + 5  # fit.init, fit.evolve, outside spans
    assert total == pytest.approx((100 - left) / GENS)


@pytest.mark.parametrize("name", ["init_idle_ms.fit", "boundary_idle_ms.fit"])
def test_no_program_span_reads_nothing(name):
    """A program without the spans (the harness's own alone) prints
    nothing rather than 0."""
    assert metric_reader(name)(_ctx(_reduce(HARNESS))) is None
    assert metric_reader(name)(_ctx(None)) is None


def test_each_reader_needs_its_own_spans():
    boundary_only = _reduce(HARNESS + [s for s in PROGRAM
                                       if not s[0].startswith("fit.init")])
    c = _ctx(boundary_only)
    assert metric_reader("init_idle_ms.fit")(c) is None
    assert metric_reader("boundary_idle_ms.fit")(c) == pytest.approx(1.4)
