"""HLO cost analyzer: must match XLA on loop-free programs and correctly
multiply while-loop bodies by their trip counts (where XLA undercounts)."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from benchmarks.hlo_cost import HloAnalyzer, analyze_hlo_text  # noqa: E402


def _compile(f, *shapes):
    return jax.jit(f).lower(*shapes).compile()


def test_matches_xla_loop_free():
    def f(x, w):
        return jnp.tanh(x @ w) @ w.T

    c = _compile(f, jax.ShapeDtypeStruct((64, 128), jnp.float32),
                 jax.ShapeDtypeStruct((128, 128), jnp.float32))
    got = analyze_hlo_text(c.as_text())
    want = c.cost_analysis()["flops"]
    assert abs(got["flops"] - want) / want < 0.05


def test_scan_multiplied_by_trip_count():
    def g(x):
        return jax.lax.scan(lambda c, _: (c @ c, None), x, None, length=10)[0]

    c = _compile(g, jax.ShapeDtypeStruct((256, 256), jnp.float32))
    got = analyze_hlo_text(c.as_text())
    expect = 10 * 2 * 256**3
    assert abs(got["flops"] - expect) / expect < 0.05
    # and the built-in analysis indeed undercounts (the reason we exist)
    assert c.cost_analysis()["flops"] < expect / 5


def test_nested_scans_compose():
    def body_inner(c, _):
        return c @ c, None

    def body_outer(c, _):
        c2, _ = jax.lax.scan(body_inner, c, None, length=3)
        return c2, None

    def f(x):
        return jax.lax.scan(body_outer, x, None, length=4)[0]

    c = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    got = analyze_hlo_text(c.as_text())
    expect = 4 * 3 * 2 * 128**3
    assert abs(got["flops"] - expect) / expect < 0.05


def test_computation_split_robust():
    def f(x):
        return jnp.sum(jax.nn.softmax(x @ x))

    c = _compile(f, jax.ShapeDtypeStruct((64, 64), jnp.float32))
    a = HloAnalyzer(c.as_text())
    assert len(a.computations) >= 1
    cost = a.entry_cost()
    assert cost.flops >= 2 * 64**3
    assert cost.bytes > 0


def test_collectives_counted(tmp_path):
    text = """HloModule test

ENTRY %main.1 (p0: f32[16,128]) -> f32[16,128] {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ag = f32[32,128]{1,0} all-gather(%p0), replica_groups={}, dimensions={0}
  %slice = f32[16,128]{1,0} slice(%ag), slice={[0:16], [0:128]}
  ROOT %ar = f32[16,128]{1,0} all-reduce(%slice), to_apply=%add
}
"""
    got = analyze_hlo_text(text)
    assert got["collectives"]["all-gather"] == 32 * 128 * 4
    assert got["collectives"]["all-reduce"] == 16 * 128 * 4
