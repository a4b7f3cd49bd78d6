"""The plain reference against the program's own host re-score (the
scalar interpreter `chip_smoke.py` uses), and its rejection of a wrong
published fitness."""
import math

import numpy as np
import pytest

import reference
from reference import interp


@pytest.mark.parametrize("genome", ["tree", "postfix"])
@pytest.mark.parametrize("kernel", ["r", "c", "mse", "pearson"])
def test_reference_reproduces_the_host_rescore(kernel, genome):
    import jax

    from repro.core import primitives as prim
    from repro.core.scalar_eval import fitness_scalar
    from repro.core.trees import TreeSpec, generate_population, to_string

    fn_set = prim.CLASSIFY_SET if kernel == "c" else prim.KITCHEN_SINK
    spec = TreeSpec(max_depth=4, n_features=3, fn_set=fn_set, genome=genome)
    op, arg = generate_population(jax.random.PRNGKey(3), 12, spec)
    op, arg = np.asarray(op), np.asarray(arg)
    rng = np.random.RandomState(0)
    X = rng.randn(40, 3).astype(np.float32)
    y = (rng.rand(40) > 0.5).astype(np.float32) if kernel == "c" else \
        (X[:, 0] * X[:, 1]).astype(np.float32)
    consts = np.asarray(spec.const_table())
    host = fitness_scalar(op, arg, X, y, consts, kernel=kernel, n_classes=2,
                          genome=genome)
    for o, a, want in zip(op, arg, host):
        got = reference.score(to_string(o, a, const_table=consts, genome=genome),
                              X, y, kernel, n_classes=2)
        assert reference.gap(float(want), got) <= 1e-5, (kernel, want, got)


def test_reference_rejects_a_perturbed_fitness():
    rng = np.random.RandomState(1)
    X = rng.randn(1000, 2).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    ref = reference.score("(x0 + 1)", X, y, "c")
    assert ref == -float((np.clip(np.round(X[:, 0] + 1), 0, 1) == y).sum())
    assert reference.gap(ref, ref) == 0.0
    assert reference.gap(ref + 1, ref) > 0  # one hit off is seen
    assert reference.gap(math.inf, ref) == math.inf
    assert reference.gap(math.inf, math.inf) == 0.0


def test_parser_reads_the_published_grammar():
    node = interp.parse("min((x0 / -3), abs((x2 - 4)))")
    assert node == ("min", ("div", ("x", 0), ("k", -3.0)),
                    ("abs", ("sub", ("x", 2), ("k", 4.0))))
    X = np.array([[6.0, 0.0, 1.0], [0.0, 0.0, 9.0]], np.float32)
    np.testing.assert_array_equal(interp.evaluate(node, X), [-2.0, 0.0])
    # protected division: |b| < 1e-9 gives 1
    np.testing.assert_array_equal(
        interp.evaluate(interp.parse("(x0 / x1)"), X), [1.0, 1.0])
    with pytest.raises(ValueError):
        interp.parse("(x0 ^ x1)")


def test_bfloat16_control_rounds_every_node():
    X = np.array([[1.0 + 2 ** -10]], np.float32)
    node = interp.parse("(x0 * 3)")
    assert interp.evaluate(node, X)[0] == np.float32(1.0 + 2 ** -10) * 3
    assert interp.evaluate(node, X, "bfloat16")[0] == 3.0


def _ulp_step(v, k):
    """v moved by k float32 steps (k < 0: down)."""
    v = np.asarray(v, np.float32)
    for _ in range(abs(k)):
        v = np.nextafter(v, np.float32(np.inf if k > 0 else -np.inf))
    return v


def _perturbed(node, X, rng):
    """A float32 evaluation whose every quotient lands up to 3 steps
    from the correctly rounded one, as a division within 2.5 ulps may."""
    kind = node[0]
    if kind == "x":
        return X[:, node[1]].astype(np.float32)
    if kind == "k":
        return np.full(X.shape[0], node[1], np.float32)
    a = _perturbed(node[1], X, rng)
    if kind == "abs":
        return np.abs(a)
    b = _perturbed(node[2], X, rng)
    with np.errstate(all="ignore"):
        if kind == "div":
            small = np.abs(b) < interp.EPS
            q = a / np.where(small, np.float32(1), b)
            k = rng.randint(-3, 4, size=q.shape)
            q = np.where(k > 0, _ulp_step(q, 3), np.where(k < 0, _ulp_step(q, -3), q))
            return np.where(small, np.float32(1), q).astype(np.float32)
        return {"add": np.add, "sub": np.subtract, "mul": np.multiply,
                "min": np.minimum, "max": np.maximum}[kind](a, b).astype(np.float32)


def _classify_trees(n, seed, depth=5):
    import jax

    from repro.core import primitives as prim
    from repro.core.trees import TreeSpec, generate_population, to_string

    spec = TreeSpec(max_depth=depth, n_features=3, fn_set=prim.CLASSIFY_SET)
    op, arg = generate_population(jax.random.PRNGKey(seed), n, spec)
    consts = np.asarray(spec.const_table())
    return [to_string(o, a, const_table=consts)
            for o, a in zip(np.asarray(op), np.asarray(arg))]


def test_bounds_are_the_ieee_value_where_nothing_divides():
    rng = np.random.RandomState(2)
    X = rng.randn(500, 3).astype(np.float32)
    texts = [t for t in _classify_trees(64, 5) if "/" not in t]
    assert len(texts) >= 5
    for text in texts:
        node = interp.parse(text)
        lo, hi, nan = reference.bounds(node, X)
        want = interp.evaluate(node, X)
        np.testing.assert_array_equal(lo, want)
        np.testing.assert_array_equal(hi, want)


def test_bounds_hold_every_quotient_within_three_ulps():
    """Each row of a float32 evaluation with perturbed quotients lies in
    its bounds, and its kernel-c fitness in the range: gap 0."""
    rng = np.random.RandomState(3)
    X = rng.randn(2000, 9).astype(np.float32)
    y = (rng.rand(2000) > 0.5).astype(np.float32)
    texts = _classify_trees(64, 7) + [
        "(x0 / (x0 + x0))", "((x1 / x1) * x2)", "((x0 / 3) - (x0 / 3))",
        # a published elite whose value is 0.5 exactly on many rows in
        # IEEE float32, and an ulp above it on the chip
        "(min(max((x3 / 2), max(x4, min(-1, x2))), (min(x1, x1) * "
        "min(-1, x4))) / max((abs(max(x1, x3)) / min(-1, x0)), "
        "min(x1, (min(x5, x6) - 4))))"]
    for text in texts:
        node = interp.parse(text)
        lo, hi, nan = reference.bounds(node, X)
        rng_fit = reference.fitness_range("c", lo, hi, nan, y)
        for _ in range(3):
            v = _perturbed(node, X, rng)
            ok = np.isnan(v) & nan | ((lo <= v) & (v <= hi))
            assert ok.all(), (text, v[~ok][:3], lo[~ok][:3], hi[~ok][:3])
            f = reference.fitness("c", v, y)
            assert reference.gap(f, reference.fitness(
                "c", interp.evaluate(node, X), y), rng_fit) == 0.0, text


def test_a_tie_made_by_division_is_a_range():
    """x0 / (x0 + x0) is 0.5 on every row in IEEE float32, which labels
    each row 0; a quotient an ulp off labels rows 1. The point reference
    calls that far off, the range does not, and a count outside the
    range is still seen."""
    rng = np.random.RandomState(4)
    X = rng.randn(1000, 1).astype(np.float32)
    y = (rng.rand(1000) > 0.5).astype(np.float32)
    text = "(x0 / (x0 + x0))"
    ref = reference.score(text, X, y, "c")
    assert ref == -float((y == 0).sum())
    least, most, may_be_inf = reference.score_range(text, X, y, "c")
    assert (least, most, may_be_inf) == (-1000.0, 0.0, False)
    upward = -float((y == 1).sum())  # every quotient rounded up
    assert reference.gap(upward, ref) > 1e-3
    assert reference.gap(upward, ref, (least, most, may_be_inf)) == 0.0
    sure = reference.score_range("(x0 + 1)", X, y, "c")
    point = reference.score("(x0 + 1)", X, y, "c")
    assert sure[:2] == (point, point)
    assert reference.gap(point + 1, point, sure) > 0
    assert reference.gap(math.inf, point, sure) == math.inf


def test_a_row_that_can_only_be_nan_makes_the_tree_invalid():
    X = np.array([[np.inf], [1.0]], np.float32)
    y = np.zeros(2, np.float32)
    rng_fit = reference.score_range("(x0 - x0)", X, y, "c")
    assert rng_fit == (math.inf, math.inf, True)
    assert reference.gap(math.inf, math.inf, rng_fit) == 0.0
    assert reference.gap(-1.0, math.inf, rng_fit) == math.inf
