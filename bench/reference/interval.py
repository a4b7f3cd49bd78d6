"""Bounds on what a float32 program may compute for an expression.

Addition, subtraction, multiplication, `abs`, `min` and `max` are
correctly rounded in float32 on every platform the benchmark runs on;
division is not everywhere. The float32 that GPU and accelerator
programming models define (OpenCL 1.2 section 7.4, Vulkan's SPIR-V
precision table) allows a single-precision quotient to lie within 2.5
units in the last place (ulp) of the exact one, that is within 3 ulps of
the correctly rounded quotient. A result below the smallest normal
number may be flushed to zero.

`bounds` evaluates an expression parsed by `interp.parse` over every row
with interval arithmetic on float32 endpoints: each node's interval holds
every value such a float32 program can produce there, given the
intervals of its operands. Rounding to nearest is monotone, so the
correctly rounded operations need no widening: with point operands their
interval is the single IEEE result, and the interval widens only where a
division (or a flush to zero) can move a value. A row whose value may be
NaN is marked; a row whose value can only be NaN has an empty interval
(NaN endpoints).
"""
from __future__ import annotations

import numpy as np

from reference.interp import EPS

F32 = np.float32
INF = F32(np.inf)
TINY = np.finfo(np.float32).tiny  # the smallest normal float32
DIV_ULPS = 3
_EXACT = {"add": np.add, "sub": np.subtract, "mul": np.multiply}


def _down(q, k):
    """The float32 at or below q - k ulps of q (ulp: the spacing of
    float32 at |q|); -inf stays, +inf gives the largest finite."""
    q = np.asarray(q, F32)
    fin = np.isfinite(q)
    s = np.spacing(np.abs(np.where(fin, q, F32(1)))).astype(np.float64)
    t = q.astype(np.float64) - k * s
    with np.errstate(over="ignore"):
        r = t.astype(F32)
    r = np.where(r.astype(np.float64) > t, np.nextafter(r, -INF), r)
    r = np.where(fin, r, np.where(q > 0, np.finfo(F32).max, q))
    return r.astype(F32)


def _up(q, k):
    return -_down(-np.asarray(q, F32), k)


def _flush(lo, hi):
    """Let a value under the smallest normal be flushed to zero."""
    lo = np.where((lo > 0) & (lo < TINY), F32(0), lo)
    hi = np.where((hi < 0) & (hi > -TINY), F32(0), hi)
    return lo, hi


def _fill(lo, hi, empty):
    """Endpoints that came out NaN from operands that were not empty
    (inf - inf at a corner) widen to the whole line."""
    lo = np.where(np.isnan(lo) & ~empty, -INF, lo)
    hi = np.where(np.isnan(hi) & ~empty, INF, hi)
    return lo, hi


def _corners(pairs):
    """Hull of the corner values; a NaN corner (0 * inf, inf / inf) from
    operands that are not empty widens it to the whole line."""
    c = np.stack(pairs)
    nan = np.isnan(c).any(0)
    lo = np.where(nan, -INF, np.min(c, 0))
    hi = np.where(nan, INF, np.max(c, 0))
    return lo, hi, nan


def _quotient(la, ha, bl, bh, k):
    """Quotient bounds for b in [bl, bh] of one sign and |b| >= EPS."""
    with np.errstate(all="ignore"):
        lo, hi, nan = _corners([la / bl, la / bh, ha / bl, ha / bh])
    return _down(lo, k), _up(hi, k), nan


def _div(a, b, k):
    (la, ha, na), (lb, hb, nb) = a, b
    empty = np.isnan(la) | np.isnan(lb)
    prot = (lb < EPS) & (hb > -EPS)  # some b has |b| < EPS: gives 1
    pos = hb >= EPS  # some b >= EPS
    neg = lb <= -EPS  # some b <= -EPS
    lo = np.where(prot, F32(1), INF)
    hi = np.where(prot, F32(1), -INF)
    nan = na | nb
    for part, bl, bh in ((pos, np.maximum(lb, EPS), hb),
                         (neg, lb, np.minimum(hb, -EPS))):
        plo, phi, pnan = _quotient(la, ha, bl, bh, k)
        lo = np.where(part, np.minimum(lo, plo), lo)
        hi = np.where(part, np.maximum(hi, phi), hi)
        nan = nan | (part & pnan)
    lo = np.where(empty, np.nan, lo).astype(F32)
    hi = np.where(empty, np.nan, hi).astype(F32)
    return lo, hi, nan


def bounds(node, X_rows: np.ndarray, div_ulps: int = DIV_ULPS):
    """(lo, hi, maybe_nan) over the rows of X_rows for one parsed
    expression: f32[rows] endpoints (NaN where the value can only be
    NaN) and bool[rows] where it may be NaN."""
    rows = X_rows.shape[0]
    cols = {}

    def point(v):
        v = np.asarray(v, F32)
        lo, hi = _flush(v, v)
        return lo, hi, np.isnan(v)

    def ev(n):
        kind = n[0]
        if kind == "x":
            if n[1] not in cols:
                cols[n[1]] = point(X_rows[:, n[1]])
            return cols[n[1]]
        if kind == "k":
            return point(np.full(rows, n[1], F32))
        a = ev(n[1])
        la, ha, na = a
        if len(n) == 2:
            if kind != "abs":
                raise ValueError(f"no interval rule for {kind!r}")
            lo = np.where(np.isnan(la), np.nan, np.where(
                la >= 0, la, np.where(ha <= 0, -ha, F32(0))))
            hi = np.maximum(np.abs(la), np.abs(ha))
            return lo.astype(F32), hi.astype(F32), na
        b = ev(n[2])
        lb, hb, nb = b
        empty = np.isnan(la) | np.isnan(lb)
        with np.errstate(all="ignore"):
            if kind == "add":
                lo, hi = _fill(la + lb, ha + hb, empty)
                nan = ((ha == INF) & (lb == -INF)) | ((la == -INF) & (hb == INF))
            elif kind == "sub":
                lo, hi = _fill(la - hb, ha - lb, empty)
                nan = ((ha == INF) & (hb == INF)) | ((la == -INF) & (lb == -INF))
            elif kind == "mul":
                lo, hi, nan = _corners([la * lb, la * hb, ha * lb, ha * hb])
                lo = np.where(empty, np.nan, lo)
                hi = np.where(empty, np.nan, hi)
            elif kind == "div":
                lo, hi, nan = _div(a, b, div_ulps)
            elif kind == "min":
                lo, hi, nan = np.minimum(la, lb), np.minimum(ha, hb), False
            elif kind == "max":
                lo, hi, nan = np.maximum(la, lb), np.maximum(ha, hb), False
            else:
                raise ValueError(f"no interval rule for {kind!r}")
            if kind in _EXACT:  # point operands: the IEEE result itself
                pt = (la == ha) & (lb == hb)
                v = _EXACT[kind](la, lb)
                lo, hi = np.where(pt, v, lo), np.where(pt, v, hi)
                nan = np.where(pt, np.isnan(v), nan)
        lo, hi = _flush(np.asarray(lo, F32), np.asarray(hi, F32))
        return lo, hi, na | nb | np.asarray(nan) | empty

    return ev(node)
