"""Pallas TPU kernel: fused GP population evaluation + fitness reduction.

This is the compute hot spot the paper optimizes (§2.5: "the evaluation of
the multivariate expression derived from each GP tree against the entire
training dataset"). The pure-jnp path (kernels/ref.py → core/eval.py)
materializes a [pop, nodes, data] intermediate in HBM between the
level-sweep and the fitness reduction; this kernel keeps the whole
evaluation frontier in VMEM per (population-tile × data-tile) block and
writes back only the [pop] fitness partials — turning a memory-bound
HBM-streaming computation into a VMEM-resident one.

Layout rules every kernel here follows, because they are what Mosaic (the
TPU kernel compiler) accepts:

  * every value the body computes on is 2-D f32 `[pop_tile, data_tile]`
    (trees on sublanes, data points on lanes) or a `[pop_tile, 1]` column
    that broadcasts across the data lanes;
  * a genome slot — static (tree level sweep) or dynamic (postfix
    instruction pointer) — is read as a lane-masked row sum (`_column`),
    never as a lane slice or a lane-dynamic index;
  * opcode dispatch is a `jnp.where` chain, never `jnp.select`;
  * terminal lookup is a one-hot matmul on the MXU against the
    terminal table (features stacked on constants), split into bf16
    parts that rebuild the f32 entry exactly, non-finite entries
    included (`terminal_table`), so the lookup returns the entry bit
    for bit;
  * per-row integer inputs (`lens`) are `[P, 1]` blocks, and the dedup
    row gather reads scalar-prefetched row ids from SMEM.

Grid: (pop_tiles, data_tiles); the data dimension is innermost so each
population tile's output block stays resident while fitness partials
accumulate across data tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import fitness as fit
from repro.core import primitives as prim

_FN_BASE = 3
_SPLIT = 3  # bf16 value parts per f32 table entry: 3 × 8 significand bits ≥ 24
TABLE_PARTS = _SPLIT + 1  # + one part coding non-finite entries
_FINITE, _NAN, _POS_INF = 0.0, 1.0, 2.0  # codes of that part; 3.0 is -inf


def interpret_mode(interpret: bool | None = None) -> bool:
    """The one place the Pallas interpret flag is decided. An explicit
    value wins; otherwise kernels compile for the chip on a TPU backend
    and run in the interpreter (the CPU test mode) everywhere else."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def terminal_table(X, const_table):
    """bf16[4, F + C, D] terminal table: feature rows stacked on constant
    rows (each constant broadcast over the data), as three bf16 parts
    whose f32 sum is exactly the entry where it is finite, plus a part
    coding the non-finite entries (NaN, +inf, -inf; 0 where finite).
    Every part is finite and exact in bf16, so a one-hot matmul against
    each is exact on any MXU precision — and a NaN data point cannot
    leak into other rows through 0 · NaN.

    Each value part is the remainder truncated to its top 8 significand
    bits by masking the low 16 bits of its f32 pattern, so it converts
    to bf16 exactly. A round trip through bf16 (`x.astype(bf16)` then
    back) would not do: XLA on the TPU may drop that cast pair as excess
    precision, which leaves the first part unrounded and the rest zero."""
    F, D = X.shape
    table = jnp.concatenate(
        [X.astype(jnp.float32),
         jnp.broadcast_to(const_table.astype(jnp.float32)[:, None],
                          (const_table.shape[0], D))], axis=0)
    finite = jnp.isfinite(table)
    parts, rest = [], jnp.where(finite, table, 0.0)
    for _ in range(_SPLIT):
        bits = jax.lax.bitcast_convert_type(rest, jnp.uint32)
        part = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
        parts.append(part.astype(jnp.bfloat16))
        rest = rest - part
    code = jnp.where(finite, _FINITE, jnp.where(
        jnp.isnan(table), _NAN, jnp.where(table > 0, _POS_INF, 3.0)))
    parts.append(code.astype(jnp.bfloat16))
    return jnp.stack(parts)


def _column(a, t):
    """a[:, t] as an f32 [rows, 1] column for a static or traced t: a
    lane-masked row sum (exactly one lane survives), which lowers where a
    lane slice or a lane-dynamic index does not."""
    lane = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1)
    return jnp.sum(jnp.where(lane == t, a, 0.0), axis=1, keepdims=True)


def _terminal_index(op, arg, n_features: int):
    """Row of the terminal table a slot reads: its feature, F + its
    constant, or -1 (matches no row → 0.0) for EMPTY and function slots."""
    return jnp.where(op == prim.FEATURE, arg,
                     jnp.where(op == prim.CONST, n_features + arg, -1.0))


def _lookup(idx, table_ref):
    """f32[R, Db] rows table[idx] of the split terminal table, idx an f32
    [R, 1] column: exact bf16 one-hot matmuls, the value parts summed in
    f32 and the non-finite code applied on top."""
    K = table_ref.shape[1]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (idx.shape[0], K), 1)
              .astype(jnp.float32) == idx).astype(jnp.bfloat16)
    dims = (((1,), (0,)), ((), ()))
    hi, mid, lo, code = (
        jax.lax.dot_general(onehot, table_ref[s], dims,
                            preferred_element_type=jnp.float32)
        for s in range(TABLE_PARTS))
    out = (hi + mid) + lo
    return jnp.where(code == _FINITE, out, jnp.where(
        code == _NAN, jnp.nan, jnp.where(code == _POS_INF, jnp.inf, -jnp.inf)))


def _apply_function_inline(op, lhs, rhs, fn_codes=None):
    """Branch-free opcode dispatch (same contract as primitives.apply_function,
    restated here so the kernel body has no module-level closure surprises).
    fn_codes restricts the chain to the run's operator set; codes are
    distinct, so the chain picks exactly what `jnp.select` would."""
    codes = (list(fn_codes) if fn_codes is not None
             else list(range(_FN_BASE, _FN_BASE + len(prim.FUNCTIONS))))
    out = jnp.zeros_like(lhs)
    for c in codes:
        out = jnp.where(op == c, prim.FUNCTIONS[c - _FN_BASE].fn(lhs, rhs), out)
    return out


def _accumulate_moments(out_ref, preds, y_ref, w_ref, *, kernel: str,
                        n_classes: int, precision: float):
    """Fused moment epilogue shared by every kernel. Phase 1 of the
    two-pass protocol: the registered FitnessKernel's `moments` (pure
    jnp, so it traces inside the Pallas body) runs on this block's
    predictions, and the [Pb, M] partials accumulate across the data
    grid (innermost grid dim revisits the out block) by elementwise sum
    or the kernel's pairwise combine. Decomposable kernels are the M=1
    case; two-pass kernels (pearson, r2) finalize in ops.fitness."""
    j = pl.program_id(1)
    spec = fit.FitnessSpec(kernel, n_classes=n_classes, precision=precision)
    kern = fit.get_kernel(kernel)
    partial = kern.moments(preds, y_ref[0], w_ref[0], spec)  # [Pb, M]

    @pl.when(j == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(j != 0)
    def _acc():
        out_ref[...] = kern.merge_moments(out_ref[...], partial, spec)


def _eval_fitness_kernel(op_ref, arg_ref, table_ref, y_ref, w_ref, out_ref,
                         *, n_features: int, kernel: str, n_classes: int,
                         precision: float, fn_codes=None):
    """One (pop_tile, data_tile) block: evaluate + reduce fitness partial."""
    ops = op_ref[...].astype(jnp.float32)  # [Pb, N] small ints, exact in f32
    args = arg_ref[...].astype(jnp.float32)
    Pb, N = ops.shape
    opc = [_column(ops, i) for i in range(N)]  # [Pb, 1] per slot

    # ---- terminal values for every slot: ONE node-major lookup ------------
    idx = jnp.concatenate(
        [_terminal_index(opc[i], _column(args, i), n_features)
         for i in range(N)], axis=0)  # [N*Pb, 1]
    term = _lookup(idx, table_ref)  # [N*Pb, Db]; EMPTY slots read 0.0

    # ---- bottom-up heap sweep, every slot a [Pb, Db] VMEM value ------------
    vals = [None] * N
    for i in range(N - 1, -1, -1):
        node = term[i * Pb:(i + 1) * Pb]
        if 2 * i + 2 < N:
            fn = _apply_function_inline(opc[i], vals[2 * i + 1],
                                        vals[2 * i + 2], fn_codes)
            node = jnp.where(opc[i] >= _FN_BASE, fn, node)
        vals[i] = node
    _accumulate_moments(out_ref, vals[0], y_ref, w_ref, kernel=kernel,
                        n_classes=n_classes, precision=precision)


def _eval_fitness_postfix_kernel(op_ref, arg_ref, len_ref, table_ref,
                                 y_ref, w_ref, out_ref, stack_ref, *,
                                 n_features: int, kernel: str,
                                 n_classes: int, precision: float,
                                 fn_codes=None):
    """One (pop_tile, data_tile) block of the postfix stack interpreter.

    Instead of the tree kernel's level sweep over all NODES slots, each
    iteration executes ONE postfix instruction for the whole tile: a
    `fori_loop` whose trip count is the tile's max active length — with
    ops.py sorting rows by length, short-program tiles finish early,
    which is where the linear genome's speedup comes from.

    Per-instruction state is a shift-register operand stack of S
    [Pb, Db] VMEM scratch slots with S = TreeSpec.stack_size = max_depth + 1
    (invariant P5 bounds the operand depth, so S slots always suffice).
    Slot 0 is the top: terminals shift-push their value, unary functions
    replace the top, binary functions fold the top two and shift up.
    Both operands are the top two slots by construction — no
    result-buffer gather at all, and the carried state is S/N of the
    res-buffer alternative's VMEM (the win that lets data tiles grow).
    Rows shorter than the tile's trip count hold their stack through the
    EMPTY tail (P1 makes the tail contiguous), so preds is simply the
    final top-of-stack.
    """
    ops = op_ref[...].astype(jnp.float32)  # [Pb, N]
    args = arg_ref[...].astype(jnp.float32)
    S, Pb, Db = stack_ref.shape

    codes = (list(fn_codes) if fn_codes is not None
             else list(range(_FN_BASE, _FN_BASE + len(prim.FUNCTIONS))))
    bin_codes = [c for c in codes if prim.ARITY[c] == 2]

    def body(t, carry):
        stack = tuple(stack_ref[k] for k in range(S))
        opt = _column(ops, t)  # [Pb, 1]
        tval = _lookup(_terminal_index(opt, _column(args, t), n_features),
                       table_ref)  # [Pb, Db]

        # function value: operands are the stack's top two slots (rhs =
        # top — postfix emits the right subtree last)
        top, sec = stack[0], stack[1]
        is_bin = jnp.zeros((Pb, 1), jnp.bool_)
        for c in bin_codes:
            is_bin = is_bin | (opt == c)
        lhs = jnp.where(is_bin, sec, top)
        fnv = _apply_function_inline(opt, lhs, top, fn_codes)

        zero = jnp.zeros((Pb, Db), jnp.float32)
        push = (tval,) + stack[:S - 1]
        una = (fnv,) + stack[1:]
        binr = (fnv,) + stack[2:] + (zero,)
        is_term = opt < _FN_BASE
        # EMPTY tail: hold, so a finished row's result stays on top while
        # longer rows in the tile keep executing
        hold = opt == prim.EMPTY
        for k, (s, p, u, b) in enumerate(zip(stack, push, una, binr)):
            stack_ref[k] = jnp.where(hold, s, jnp.where(is_term, p,
                                                        jnp.where(is_bin, b, u)))
        return carry

    stack_ref[...] = jnp.zeros(stack_ref.shape, jnp.float32)
    trip = jnp.max(len_ref[...])  # dynamic: sorted tiles of short programs exit early
    jax.lax.fori_loop(0, trip, body, 0)
    _accumulate_moments(out_ref, stack_ref[0], y_ref, w_ref, kernel=kernel,
                        n_classes=n_classes, precision=precision)


def _data_specs(data_tile: int):
    """BlockSpecs of the [1, D] target and weight rows (the same for
    every kernel; the index map ignores any scalar-prefetch refs)."""
    spec = pl.BlockSpec((1, data_tile), lambda i, j, *_: (0, j))
    return [spec, spec]


def _moment_out(P: int, pop_tile: int, kernel: str):
    n_moments = fit.get_kernel(kernel).n_moments
    return (pl.BlockSpec((pop_tile, n_moments), lambda i, j, *_: (i, 0)),
            jax.ShapeDtypeStruct((P, n_moments), jnp.float32))


def _rows(v):
    return v.astype(jnp.float32).reshape(1, -1)


def eval_fitness_pallas_postfix(op, arg, lens, X, y, weight,
                                const_table, *, stack_size: int,
                                kernel: str = "r",
                                n_classes: int = 3, precision: float = 1e-4,
                                pop_tile: int = 8, data_tile: int = 1024,
                                interpret: bool | None = None, fn_codes=None):
    """Fused postfix eval+moments over pre-padded inputs.

    op, arg:  int32[P, N]   postfix streams, P % pop_tile == 0
    lens:     int32[P]      active lengths (sort rows by length upstream so
                            tiles of short programs take short fori trips)
    X:        f32[F, D]     D % data_tile == 0
    returns   f32[P, M]     accumulated weighted moments, same contract as
                            eval_fitness_pallas

    `stack_size` is TreeSpec.stack_size (= max_depth + 1), the operand-
    stack bound invariant P5 guarantees.
    """
    P, N = op.shape
    F, D = X.shape
    assert P % pop_tile == 0 and D % data_tile == 0, (P, D, pop_tile, data_tile)
    table = terminal_table(X, const_table)
    K = table.shape[1]
    out_spec, out_shape = _moment_out(P, pop_tile, kernel)
    body = functools.partial(
        _eval_fitness_postfix_kernel, n_features=F,
        kernel=kernel, n_classes=n_classes, precision=precision,
        fn_codes=fn_codes)
    return pl.pallas_call(
        body,
        name="gp_postfix_eval",
        grid=(P // pop_tile, D // data_tile),
        in_specs=[
            pl.BlockSpec((pop_tile, N), lambda i, j: (i, 0)),
            pl.BlockSpec((pop_tile, N), lambda i, j: (i, 0)),
            pl.BlockSpec((pop_tile, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((TABLE_PARTS, K, data_tile), lambda i, j: (0, 0, j)),
            *_data_specs(data_tile),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((stack_size, pop_tile, data_tile),
                                   jnp.float32)],
        interpret=interpret_mode(interpret),
    )(op, arg, lens.astype(jnp.int32).reshape(P, 1), table, _rows(y),
      _rows(weight))


def _fitness_from_subtrees_kernel(root_ref, uniq_ref, y_ref, w_ref, out_ref,
                                  rows_ref, *, kernel: str, n_classes: int,
                                  precision: float):
    """One (pop_tile, data_tile) block of the dedup'd eval: predictions
    are a row-gather from the precomputed unique-subexpression scratch
    (core/eval.evaluate_unique_subtrees), so the per-tree work collapses
    to ONE row copy plus the fused moment epilogue — the interpreter ran
    once per DISTINCT subtree, not once per tree. Row ids come from SMEM
    (scalar prefetch), so each copy is a sublane-dynamic VMEM read: exact
    for any value, inf and NaN included."""
    Pb = rows_ref.shape[0]
    base = pl.program_id(0) * Pb
    for r in range(Pb):
        rows_ref[pl.ds(r, 1), :] = uniq_ref[pl.ds(root_ref[base + r], 1), :]
    _accumulate_moments(out_ref, rows_ref[...], y_ref, w_ref, kernel=kernel,
                        n_classes=n_classes, precision=precision)


def eval_fitness_pallas_from_subtrees(root, uniq, y, weight, *,
                                      kernel: str = "r", n_classes: int = 3,
                                      precision: float = 1e-4,
                                      pop_tile: int = 8,
                                      data_tile: int = 1024,
                                      interpret: bool | None = None):
    """Fused gather+moments over precomputed unique-subtree outputs.

    root:  int32[P]     unique-slot id per tree (DedupPlan.root),
                        P % pop_tile == 0
    uniq:  f32[U, D]    unique-subexpression values, D % data_tile == 0
    returns f32[P, M]   accumulated weighted moments — same contract,
                        same (pop, data) grid, same j==0/j!=0 merge
                        order as eval_fitness_pallas_postfix, so moments
                        are BITWISE identical whenever the tile geometry
                        matches the plain kernel's.
    """
    (P,) = root.shape
    U, D = uniq.shape
    assert P % pop_tile == 0 and D % data_tile == 0, (P, D, pop_tile, data_tile)
    out_spec, out_shape = _moment_out(P, pop_tile, kernel)
    body = functools.partial(
        _fitness_from_subtrees_kernel, kernel=kernel, n_classes=n_classes,
        precision=precision)
    return pl.pallas_call(
        body,
        name="gp_subtree_eval",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(P // pop_tile, D // data_tile),
            in_specs=[
                pl.BlockSpec((U, data_tile), lambda i, j, root: (0, j)),
                *_data_specs(data_tile),
            ],
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM((pop_tile, data_tile), jnp.float32)]),
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(jnp.clip(root, 0, U - 1).astype(jnp.int32), uniq.astype(jnp.float32),
      _rows(y), _rows(weight))


def _fitness_from_preds_kernel(preds_ref, y_ref, w_ref, out_ref, *,
                               kernel: str, n_classes: int, precision: float):
    """One (pop_tile, data_tile) block of the spilled dedup epilogue:
    predictions were gathered from the unique-subtree table at the XLA
    level (HBM-resident `uniq[root]`), so the block only streams its own
    pop_tile rows — no U-row scratch in VMEM."""
    _accumulate_moments(out_ref, preds_ref[...], y_ref, w_ref, kernel=kernel,
                        n_classes=n_classes, precision=precision)


def eval_fitness_pallas_from_preds(preds, y, weight, *, kernel: str = "r",
                                   n_classes: int = 3, precision: float = 1e-4,
                                   pop_tile: int = 8, data_tile: int = 1024,
                                   interpret: bool | None = None):
    """Fused moments over pre-gathered predictions.

    preds:  f32[P, D]   per-tree predictions (`uniq[DedupPlan.root]`
                        materialized at the XLA level), P % pop_tile == 0,
                        D % data_tile == 0
    returns f32[P, M]   accumulated weighted moments — same contract,
                        same (pop, data) grid, same j==0/j!=0 merge order
                        as eval_fitness_pallas_postfix, so moments are
                        BITWISE identical at the same tile geometry.

    This is the dedup spill path: when the f32[U, Db] unique-subtree
    scratch of `eval_fitness_pallas_from_subtrees` would not fit VMEM at
    the plain kernel's tile pick, `ops._moments_padded` gathers in HBM
    and streams (pop_tile, data_tile) blocks here instead of shrinking
    the data tile — shrinking would change the merge order and break the
    dedup-off/dedup-on bitwise contract.
    """
    P, D = preds.shape
    assert P % pop_tile == 0 and D % data_tile == 0, (P, D, pop_tile, data_tile)
    out_spec, out_shape = _moment_out(P, pop_tile, kernel)
    body = functools.partial(
        _fitness_from_preds_kernel, kernel=kernel, n_classes=n_classes,
        precision=precision)
    return pl.pallas_call(
        body,
        name="gp_preds_eval",
        grid=(P // pop_tile, D // data_tile),
        in_specs=[
            pl.BlockSpec((pop_tile, data_tile), lambda i, j: (i, j)),
            *_data_specs(data_tile),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(preds.astype(jnp.float32), _rows(y), _rows(weight))


def eval_fitness_pallas(op, arg, X, y, weight, const_table, *,
                        kernel: str = "r", n_classes: int = 3, precision: float = 1e-4,
                        pop_tile: int = 8, data_tile: int = 1024,
                        interpret: bool | None = None, fn_codes=None):
    """Fused eval+moments over pre-padded inputs.

    op, arg:  int32[P, N]   P % pop_tile == 0
    X:        f32[F, D]     D % data_tile == 0
    y, weight f32[D]        weight is 1.0 on valid points, 0.0 on padding —
                            both the wrapper's tile padding AND any dataset
                            padding the caller threaded in (loader.pad_rows),
                            composed upstream in ops.fitness
    returns   f32[P, M]     the kernel's fully-accumulated weighted moments
                            (M = FitnessKernel.n_moments; for decomposable
                            kernels M == 1 and [:, 0] is the fitness);
                            finalize with FitnessKernel.reduce_moments
    """
    P, N = op.shape
    F, D = X.shape
    assert P % pop_tile == 0 and D % data_tile == 0, (P, D, pop_tile, data_tile)
    table = terminal_table(X, const_table)
    K = table.shape[1]
    out_spec, out_shape = _moment_out(P, pop_tile, kernel)
    body = functools.partial(
        _eval_fitness_kernel, n_features=F,
        kernel=kernel, n_classes=n_classes, precision=precision,
        fn_codes=fn_codes)
    return pl.pallas_call(
        body,
        name="gp_tree_eval",
        grid=(P // pop_tile, D // data_tile),
        in_specs=[
            pl.BlockSpec((pop_tile, N), lambda i, j: (i, 0)),
            pl.BlockSpec((pop_tile, N), lambda i, j: (i, 0)),
            pl.BlockSpec((TABLE_PARTS, K, data_tile), lambda i, j: (0, 0, j)),
            *_data_specs(data_tile),
        ],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret_mode(interpret),
    )(op, arg, table, _rows(y), _rows(weight))
