"""Plain reference for GP expressions: parse the infix string a run
publishes and evaluate it over every data row with NumPy.

It shares nothing with the program under test: no opcode table, no
constant table, no kernel. It reads the grammar the program prints for
a champion (`(a + b)`, `(a - b)`, `(a * b)`, `(a / b)`, `name(a)`,
`name(a, b)`, `x<i>` for feature i, and numeric constants) and follows
the semantics of Karoo GP's protected operators as the paper states them:
division by |b| < 1e-9 gives 1, `log` takes log(|a| + 1e-9), `sqrt`
takes sqrt(|a|).

The per-point scalar interpreter of the paper's baseline evaluates one
row at a time; this one evaluates each node over all rows at once with
the same per-node rounding, which is the same arithmetic in the same
order for every row.

`dtype="float32"` is the reference. `dtype="bfloat16"` rounds the
inputs and every node's result to bfloat16: the control, one precision
below what the configurations state, which the comparison must reject.
"""
from __future__ import annotations

import re

import numpy as np

EPS = np.float32(1e-9)
_TOKEN = re.compile(r"\s*([A-Za-z_]\w*|\d+(?:\.\d*)?(?:e[-+]?\d+)?|[(),+\-*/])")
_INFIX = {"+": "add", "-": "sub", "*": "mul", "/": "div"}
_BINARY = {"add", "sub", "mul", "div", "min", "max"}
_UNARY = {"neg", "abs", "sin", "cos", "sqrt", "log", "square"}


def tokenize(text: str) -> list[str]:
    out, i = [], 0
    text = text.strip()
    while i < len(text):
        m = _TOKEN.match(text, i)
        if m is None:
            raise ValueError(f"cannot read {text[i:i + 16]!r}")
        out.append(m.group(1))
        i = m.end()
    return out


def parse(text: str):
    """Infix text -> nested tuples: ("x", i) | ("k", value) |
    (op, child) | (op, lhs, rhs)."""
    toks = tokenize(text)
    pos = 0

    def peek():
        return toks[pos] if pos < len(toks) else None

    def take(want=None):
        nonlocal pos
        tok = peek()
        if tok is None or (want is not None and tok != want):
            raise ValueError(f"expected {want!r} at token {pos} of {text!r}")
        pos += 1
        return tok

    def expr():
        tok = peek()
        if tok == "(":
            take("(")
            lhs = expr()
            sym = take()
            if sym not in _INFIX:
                raise ValueError(f"unknown infix operator {sym!r}")
            rhs = expr()
            take(")")
            return (_INFIX[sym], lhs, rhs)
        if tok == "-":  # a negative constant
            take("-")
            return ("k", -float(take()))
        if tok is not None and tok[0].isdigit():
            return ("k", float(take()))
        name = take()
        if peek() == "(":
            take("(")
            a = expr()
            if name in _BINARY:
                take(",")
                b = expr()
                take(")")
                return (name, a, b)
            if name not in _UNARY:
                raise ValueError(f"unknown function {name!r}")
            take(")")
            return (name, a)
        m = re.fullmatch(r"x(\d+)", name)
        if m is None:
            raise ValueError(f"unknown terminal {name!r}")
        return ("x", int(m.group(1)))

    node = expr()
    if pos != len(toks):
        raise ValueError(f"trailing tokens in {text!r}")
    return node


def _rounder(dtype: str):
    if dtype == "float32":
        return lambda a: np.asarray(a, np.float32)
    if dtype == "bfloat16":
        import ml_dtypes

        return lambda a: np.asarray(a, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown reference dtype {dtype!r}")


def evaluate(node, X_rows: np.ndarray, dtype: str = "float32") -> np.ndarray:
    """Predictions f32[rows] of one parsed expression over X_rows
    [rows, features], rounding every node's value to `dtype`."""
    q = _rounder(dtype)
    rows = X_rows.shape[0]
    cols = {}

    def ev(n):
        kind = n[0]
        if kind == "x":
            if n[1] not in cols:
                cols[n[1]] = q(X_rows[:, n[1]])
            return cols[n[1]]
        if kind == "k":
            return q(np.full(rows, n[1], np.float32))
        a = ev(n[1])
        b = ev(n[2]) if len(n) == 3 else None
        with np.errstate(all="ignore"):
            if kind == "add":
                r = a + b
            elif kind == "sub":
                r = a - b
            elif kind == "mul":
                r = a * b
            elif kind == "div":
                small = np.abs(b) < EPS
                r = np.where(small, np.float32(1), a / np.where(small, np.float32(1), b))
            elif kind == "neg":
                r = -a
            elif kind == "abs":
                r = np.abs(a)
            elif kind == "sin":
                r = np.sin(a)
            elif kind == "cos":
                r = np.cos(a)
            elif kind == "sqrt":
                r = np.sqrt(np.abs(a))
            elif kind == "log":
                r = np.log(np.abs(a) + EPS)
            elif kind == "square":
                r = a * a
            elif kind == "min":
                r = np.minimum(a, b)
            elif kind == "max":
                r = np.maximum(a, b)
            else:
                raise ValueError(f"unknown node {kind!r}")
        return q(r)

    return ev(node)
