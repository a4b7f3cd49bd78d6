"""Pallas TPU kernels: fused GP population evaluation + fitness reduction.

This is the compute hot spot the paper optimizes (§2.5: "the evaluation of
the multivariate expression derived from each GP tree against the entire
training dataset"). The pure-jnp path (kernels/ref.py → core/eval.py)
materializes a [pop, nodes, data] intermediate in HBM between the
level-sweep and the fitness reduction; these kernels keep the evaluation
in VMEM and write back only the [pop, M] fitness moments.

The tree kernel, `gp_tree_eval` (`eval_fitness_pallas_tree`), is the one
kernel of the heap-tree genome. It evaluates one tree at a time: the
tree's opcodes are SMEM scalars, each active function slot applies only
its own operator (a scalar branch on its opcode), and every value is a
dense `[data_tile / 128, 128]` f32 slab of that tree's values over the
data tile, held in a VMEM bank and indexed on its leading axis, so a
terminal costs no vector work at all. Its grid is (data_tiles,
pop_tiles), the trees innermost, so each tile of X is read from HBM
once per generation. Its moment epilogue reads the predictions back as
`[pop_tile, moment_tile]` blocks and merges them in data order into the
pop tile's moments, which an HBM buffer carries from one data tile to
the next (so VMEM use does not grow with the population); its moments
equal `gp_postfix_eval`'s at data_tile = moment_tile, bit for bit.

The sublane kernels — `gp_postfix_eval` and the two dedup kernels —
put 8 trees on the sublanes and follow these rules, because they are
what Mosaic (the TPU kernel compiler) accepts:

  * every value the body computes on is 2-D f32 `[pop_tile, data_tile]`
    (trees on sublanes, data points on lanes) or a `[pop_tile, 1]` column
    that broadcasts across the data lanes;
  * a genome slot (the postfix instruction pointer) is read as a
    lane-masked row sum (`_column`), never as a lane slice or a
    lane-dynamic index;
  * opcode dispatch is a `jnp.where` chain, never `jnp.select`;
  * terminal lookup is a one-hot matmul on the MXU against the
    terminal table (features stacked on constants), split into bf16
    parts that rebuild the f32 entry exactly, non-finite entries
    included (`terminal_table`), so the lookup returns the entry bit
    for bit;
  * per-row integer inputs (`lens`) are `[P, 1]` blocks, and the dedup
    row gather reads scalar-prefetched row ids from SMEM.

Their grid is (pop_tiles, data_tiles); the data dimension is innermost
so each population tile's output block stays resident while fitness
partials accumulate across data tiles.
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
    """Fused moment epilogue of the sublane kernels. Phase 1 of the
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


def _postorder(n_nodes: int) -> list[int]:
    """Heap slots in post-order: both subtrees before their root."""
    def walk(s):
        if s >= n_nodes:
            return []
        return walk(2 * s + 1) + walk(2 * s + 2) + [s]
    return walk(0)


def _bank_rows(n_features: int, n_consts: int, n_nodes: int):
    """int32[N] bank row that holds heap slot s's value when it is a
    function (see `_eval_fitness_tree_kernel`): the root's row for slot
    0, one buffer per (level, side) above the leaves, and the zero slab
    at the leaves, where a function is malformed and reads as 0.0."""
    zero = n_features + n_consts
    depth = (n_nodes + 1).bit_length() - 2
    rows = []
    for s in range(n_nodes):
        level = (s + 1).bit_length() - 1
        rows.append(zero + 1 if s == 0 else
                    zero + 2 * level + s % 2 if level < depth else zero)
    return jnp.asarray(rows, jnp.int32)


def bank_slabs(n_features: int, n_consts: int, max_depth: int) -> int:
    """Slabs in the tree kernel's bank: F features, C constants, the
    zero and root slabs, and one buffer per (level, side) of the levels
    between the root and the leaves."""
    return n_features + n_consts + 2 + 2 * max(max_depth - 1, 0)


def _eval_fitness_tree_kernel(op_ref, arg_ref, row_ref, const_ref, x_hbm,
                              y_ref, w_ref, out_hbm, bank_ref, root_ref,
                              sched_ref, acc_ref, sem, *, moment_tile: int,
                              n_chunks: int,
                              chunk_rows: int, kernel: str, n_classes: int,
                              precision: float, fn_codes):
    """One (data_tile, pop_tile) block of the tree kernel.

    Every value is a dense [R, 128] f32 slab of one tree over the data
    tile (R = Db / 128 rows), held in the bank scratch `bank_ref`
    [B, R, 128]: the F feature slabs (DMA'd from HBM once per data
    tile), the C constant slabs, one zero slab (what an EMPTY slot
    reads), the root's slab and one buffer per (level, side) of the heap
    for function results (`row_ref`, from `_bank_rows`). A slot's value
    is a bank row index, a scalar: a terminal costs no vector work at
    all, and a function reads its children by index.

    The trees of the pop tile run one after another. For each, a
    branch-free scalar pass lists its function slots in post-order
    (`sched_ref`); then each listed slot applies only its own operator,
    chosen by a scalar branch on its SMEM opcode, slab chunk by chunk.
    Post-order is what lets one buffer per (level, side) suffice: a left
    child's value stays in its buffer while the right subtree runs,
    which writes only the right side of the child level and deeper.

    Each root slab is copied to `root_ref` [Pb · R, 128] (tree t at rows
    [t·R, (t+1)·R)); the moment epilogue then reads the tile back as
    [Pb, moment_tile] predictions, one 128-lane column of all Pb trees
    per strided load, and merges the first `n_chunks` moment tiles of
    the data in data order — the merge order of a sublane kernel at
    data_tile = moment_tile, so the moments equal `gp_postfix_eval`'s
    for the same trees bit for bit.

    The pop tile's running [Pb, M] moments live in the first M lanes of
    `acc_ref` [Pb, 128] while the block runs: read from the HBM output
    `out_hbm` [P, 128] (a DMA that overlaps the trees) at every data
    tile after the first, and written back at the end of the block, so
    VMEM holds no buffer that grows with P. The rows are 128 lanes wide
    because Mosaic slices an HBM row for a DMA only at that width."""
    j, i = pl.program_id(0), pl.program_id(1)
    Pb, N = op_ref.shape
    F = x_hbm.shape[0]
    C = const_ref.shape[0]
    R = bank_ref.shape[1]
    zero = F + C
    codes = (list(fn_codes) if fn_codes is not None
             else list(range(_FN_BASE, _FN_BASE + len(prim.FUNCTIONS))))
    internal = [s for s in _postorder(N) if 2 * s + 1 < N]
    rows = out_hbm.at[pl.ds(pl.multiple_of(i * Pb, Pb), Pb)]
    read = pltpu.make_async_copy(rows, acc_ref, sem.at[0])

    @pl.when(j != 0)
    def _read_moments():
        read.start()

    @pl.when(i == 0)
    def _load_tile():
        pltpu.sync_copy(x_hbm.at[:, pl.ds(pl.multiple_of(j * R, 8), R), :],
                        bank_ref.at[pl.ds(0, F)])
        for c in range(C):
            bank_ref[F + c] = jnp.full((R, 128), const_ref[c], jnp.float32)
        bank_ref[zero] = jnp.zeros((R, 128), jnp.float32)

    def slab_loop(body):
        jax.lax.fori_loop(0, R // chunk_rows, lambda c, carry: (
            body(pl.multiple_of(c * chunk_rows, chunk_rows)), carry)[1], 0)

    def location(t, s):
        """Bank row of slot s's value."""
        o, a = op_ref[t, s], arg_ref[t, s]
        return jnp.where(o == prim.FEATURE, jnp.clip(a, 0, F - 1),
                         jnp.where(o == prim.CONST, F + jnp.clip(a, 0, C - 1),
                                   jnp.where(o >= _FN_BASE, row_ref[s], zero)))

    def apply(code, lhs, rhs, dst):
        fn = prim.FUNCTIONS[code - _FN_BASE].fn

        def body(r):
            a = bank_ref[lhs, pl.ds(r, chunk_rows), :]
            b = (bank_ref[rhs, pl.ds(r, chunk_rows), :]
                 if prim.ARITY[code] == 2 else a)
            bank_ref[dst, pl.ds(r, chunk_rows), :] = fn(a, b)
        slab_loop(body)

    def step(t, e, carry):
        s = sched_ref[e]
        o = op_ref[t, s]
        kids = location(t, 2 * s + 1), location(t, 2 * s + 2)
        for code in codes:
            pl.when(o == code)(functools.partial(apply, code, *kids,
                                                 row_ref[s]))
        return carry

    def tree(t, carry):
        n = jnp.int32(0)
        for s in internal:  # branch-free: every slot is written, functions kept
            sched_ref[n] = jnp.int32(s)
            n = n + (op_ref[t, s] >= _FN_BASE).astype(jnp.int32)
        jax.lax.fori_loop(0, n, functools.partial(step, t), 0)
        root = location(t, 0)
        base = pl.multiple_of(t * R, 8)

        def copy(r):
            root_ref[pl.ds(base + r, chunk_rows), :] = (
                bank_ref[root, pl.ds(r, chunk_rows), :])
        slab_loop(copy)
        return carry

    jax.lax.fori_loop(0, Pb, tree, 0)

    # ---- moment epilogue over [Pb, moment_tile] chunks, in data order ----
    spec = fit.FitnessSpec(kernel, n_classes=n_classes, precision=precision)
    kern = fit.get_kernel(kernel)
    M = kern.n_moments
    q = moment_tile // 128

    @pl.when(j != 0)
    def _wait_moments():
        read.wait()

    def chunk(k, carry):
        r0 = k * q
        preds = jnp.concatenate(
            [root_ref[pl.ds(r0 + c, Pb, stride=R), :] for c in range(q)],
            axis=1)  # [Pb, moment_tile]
        y = jnp.concatenate([y_ref[pl.ds(r0 + c, 1), :] for c in range(q)],
                            axis=1)
        w = jnp.concatenate([w_ref[pl.ds(r0 + c, 1), :] for c in range(q)],
                            axis=1)
        partial = kern.moments(preds, y[0], w[0], spec)

        @pl.when((j == 0) & (k == 0))
        def _init():
            acc_ref[:, :M] = partial

        @pl.when((j != 0) | (k != 0))
        def _acc():
            acc_ref[:, :M] = kern.merge_moments(acc_ref[:, :M], partial,
                                                spec)
        return carry

    per_tile = R // q
    jax.lax.fori_loop(0, jnp.clip(n_chunks - j * per_tile, 0, per_tile),
                      chunk, 0)
    pltpu.sync_copy(acc_ref, rows)


def _eval_fitness_postfix_kernel(op_ref, arg_ref, len_ref, table_ref,
                                 y_ref, w_ref, out_ref, stack_ref, *,
                                 n_features: int, kernel: str,
                                 n_classes: int, precision: float,
                                 fn_codes=None):
    """One (pop_tile, data_tile) block of the postfix stack interpreter.

    Instead of a level sweep over all NODES slots, each iteration
    executes ONE postfix instruction for the whole tile: a
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
    every sublane kernel; the index map ignores any scalar-prefetch
    refs)."""
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
    y, weight f32[D]        weight 1.0 on valid points, 0.0 on padding
    returns   f32[P, M]     accumulated weighted moments, same contract as
                            eval_fitness_pallas_tree

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




def eval_fitness_pallas_tree(op, arg, X, y, weight, const_table, *,
                             kernel: str = "r", n_classes: int = 3,
                             precision: float = 1e-4, pop_tile: int = 8,
                             data_tile: int = 1024, moment_tile: int = 1024,
                             n_chunks: int | None = None,
                             interpret: bool | None = None, fn_codes=None):
    """Fused eval+moments of heap trees, one tree at a time with scalar
    opcode dispatch over dense [data_tile / 128, 128] slabs of the data.

    op, arg:  int32[P, N]   heap trees, P % pop_tile == 0
    X:        f32[F, D]     D % data_tile == 0, data_tile % 1024 == 0
    y, weight f32[D]        weight is 1.0 on valid points, 0.0 on padding —
                            both the wrapper's tile padding AND any dataset
                            padding the caller threaded in (loader.pad_rows),
                            composed upstream in ops.fitness
    returns   f32[P, M]     the kernel's weighted moments (M =
                            FitnessKernel.n_moments; for decomposable
                            kernels M == 1 and [:, 0] is the fitness),
                            merged moment tile after moment tile in data
                            order (data_tile % moment_tile == 0) over the
                            first `n_chunks` moment tiles (default: all),
                            so a caller can pad D to whole data tiles
                            without merging the extra moment tiles; the
                            bits `eval_fitness_pallas_postfix` gives the
                            same trees at data_tile = moment_tile.
                            Finalize with FitnessKernel.reduce_moments.

    Grid: (data_tiles, pop_tiles), the trees innermost, so X's tile is
    read from HBM once (at the first pop tile) and serves every tree;
    each block carries its pop tile's moments from the previous data
    tile through a [P, 128] HBM buffer, so any P fits VMEM.
    """
    P, N = op.shape
    F, D = X.shape
    R = data_tile // 128
    assert (P % pop_tile == 0 and D % data_tile == 0 and R % 8 == 0
            and data_tile % moment_tile == 0 and moment_tile % 128 == 0), (
        P, D, pop_tile, data_tile, moment_tile)
    C = const_table.shape[0]
    depth = (N + 1).bit_length() - 2
    chunk_rows = next(c for c in (32, 16, 8) if R % c == 0)
    M = fit.get_kernel(kernel).n_moments
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    slab = pl.BlockSpec((R, 128), lambda j, i: (j, 0))
    body = functools.partial(
        _eval_fitness_tree_kernel, moment_tile=moment_tile,
        n_chunks=D // moment_tile if n_chunks is None else n_chunks,
        chunk_rows=chunk_rows, kernel=kernel, n_classes=n_classes,
        precision=precision, fn_codes=fn_codes)
    return pl.pallas_call(
        body,
        name="gp_tree_eval",
        grid=(D // data_tile, P // pop_tile),
        in_specs=[
            smem((pop_tile, N), lambda j, i: (i, 0)),
            smem((pop_tile, N), lambda j, i: (i, 0)),
            smem(), smem(),
            pl.BlockSpec(memory_space=pl.ANY),
            slab, slab,
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.HBM),
        out_shape=jax.ShapeDtypeStruct((P, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bank_slabs(F, C, depth), R, 128), jnp.float32),
            pltpu.VMEM((pop_tile * R, 128), jnp.float32),
            pltpu.SMEM((max(N // 2, 1),), jnp.int32),
            pltpu.VMEM((pop_tile, 128), jnp.float32),
            pltpu.SemaphoreType.DMA((1,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(op.astype(jnp.int32), arg.astype(jnp.int32),
      _bank_rows(F, C, N), const_table.astype(jnp.float32),
      X.astype(jnp.float32).reshape(F, D // 128, 128),
      y.astype(jnp.float32).reshape(-1, 128),
      weight.astype(jnp.float32).reshape(-1, 128))[:, :M]
