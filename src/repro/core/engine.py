"""KarooEngine — the generation loop, single-device and mesh-sharded.

Workflow (paper §2.4): build population → evaluate fitness → select →
apply genetic operators → repeat. Step 2 is the parallel hot spot; here it
is one jitted program per generation (`evolve_step`), or — the device-
resident fast path — one jitted program per K-generation *evolution
block* (`evolve_block` / `sharded_evolve_block`): a `lax.scan` over the
same step body, early stop as a branch-free on-device freeze, and the
per-generation best-fitness stream returned as a [K] array so the host
synchronizes once per block instead of once per generation. Under
`shard_map` the step distributes as:

    data axis   : dataset columns sharded; per-tree weighted fitness
                  moments are `psum`-reduced then finalized (the paper's
                  vectorized-evaluation axis; two-pass protocol, so even
                  pearson/r2 statistics shard here)
    model axis  : population sharded; selection needs the global fitness
                  vector + parent pool, an O(pop·nodes) `all_gather` (tiny
                  next to evaluation, paper §2.3)
    pod axis    : island-model populations with periodic elite migration
                  (core/islands.py) — the multi-pod story

Engine state is a pytree, so checkpointing/restore reuses ckpt/ unchanged.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import evolve as ev
from repro.core import fitness as fit
from repro.core import primitives as prim
from repro.core.islands import IslandConfig
from repro.core.trees import TreeSpec, generate_population
from repro.obs.trace import span as host_span


@dataclasses.dataclass(frozen=True)
class GPConfig:
    """Run-time parameters (paper Table 2 defaults).

    `island` is the first-class population layout: `islands > 1` makes
    every run `I` islands of `pop_size` trees (`op: int32[I, P, N]`) on
    ANY topology — vmapped on one device, sharded over the mesh pod axis,
    or both (see core/islands.py). `migrate_every`/`migrate_k` are legacy
    flat aliases kept for the pre-island surface: setting them away from
    their defaults folds them into `island`, and after construction they
    always mirror `island.migrate_every`/`island.migrate_k`."""

    name: str = "karoo"
    pop_size: int = 100  # trees per island (total when islands == 1)
    tree_spec: TreeSpec = TreeSpec()
    fitness: fit.FitnessSpec = fit.FitnessSpec()
    mix: ev.OperatorMix = ev.OperatorMix()
    tourn_size: int = 10
    generations: int = 30
    elitism: int = 1
    parsimony: float = 0.0  # bloat pressure: selection fitness += p * size
    stop_fitness: float | None = None  # early termination threshold (run())
    eval_impl: str = "jnp"  # any jittable name in repro.gp.backends
    data_tile: int = 1024  # pallas data-tile (lane-dim multiple of 128)
    elite_cache: bool = True  # skip re-evaluating unchanged elites
    # population-wide subexpression dedup (postfix genomes; docs/genomes.md):
    #   "off"      plain per-tree evaluation
    #   "exact"    evaluate each distinct subtree once per generation —
    #              BITWISE identical to "off" by construction (default)
    #   "semantic" exact tier + the elite fitness cache also keys on
    #              probe-batch output fingerprints (tolerance-pinned, may
    #              serve a cached fitness for a syntactically different
    #              but probe-equal elite)
    dedup: str = "exact"
    dedup_cap: int = 0  # unique-table rows; 0 = auto (max(64, pop rows))
    island: IslandConfig = IslandConfig()  # population layout + migration
    migrate_every: int = 10  # legacy alias for island.migrate_every
    migrate_k: int = 4  # legacy alias for island.migrate_k

    def __post_init__(self):
        if self.dedup not in ("off", "exact", "semantic"):
            raise ValueError(f"dedup must be 'off', 'exact' or 'semantic', "
                             f"got {self.dedup!r}")
        # fold a non-default flat alias into `island` ONLY where the
        # island itself still holds the default — an explicit
        # IslandConfig value always wins, so replacing the island on a
        # config that once used the alias can't resurrect the old value
        isl = self.island
        if self.migrate_every != 10 and isl.migrate_every == 10:
            isl = dataclasses.replace(isl, migrate_every=self.migrate_every)
        if self.migrate_k != 4 and isl.migrate_k == 4:
            isl = dataclasses.replace(isl, migrate_k=self.migrate_k)
        object.__setattr__(self, "island", isl)
        object.__setattr__(self, "migrate_every", isl.migrate_every)
        object.__setattr__(self, "migrate_k", isl.migrate_k)

    def __hash__(self):
        return hash((self.name, self.pop_size, self.tree_spec, self.fitness, self.mix,
                     self.tourn_size, self.generations, self.elitism, self.parsimony,
                     self.stop_fitness, self.eval_impl,
                     self.data_tile, self.elite_cache, self.dedup,
                     self.dedup_cap, self.island))


def cache_width(cfg: GPConfig) -> int:
    """E: rows of the cross-generation elite fitness cache carried in
    GPState. Elitism copies the E = cfg.elitism best rows into slots
    [:E] of the next population verbatim, so their fitness is already
    known — the step bodies skip re-evaluating them when the cached
    genomes match exactly (bitwise-identical by construction: the cached
    value IS last generation's evaluation of the same rows, and every
    eval path is row-independent). 0 disables (elite_cache off, no
    elitism, or degenerate all-elite populations)."""
    if cfg.elite_cache and 0 < cfg.elitism < cfg.pop_size:
        return cfg.elitism
    return 0


class GPState(NamedTuple):
    """Engine state pytree. With the classic single-population layout
    (islands == 1) the shapes are the un-batched legacy ones; with
    `GPConfig.island.islands == I > 1` every population leaf grows a
    leading island axis (`generation` stays a shared scalar — islands
    advance in lockstep):

                      islands == 1      islands == I
        key           uint32[2]         uint32[I, 2]   (fold_in(i) at init)
        op/arg        int32[P, N]       int32[I, P, N]
        fitness       f32[P]            f32[I, P]
        best_op/arg   int32[N]          int32[I, N]    (per-island champion)
        best_fitness  f32[]             f32[I]
        generation    int32[]           int32[]
        cache_op/arg  int32[E, N]       int32[I, E, N]  (elite fitness cache)
        cache_fit     f32[E]            f32[I, E]

    The cache rows (E = `cache_width(cfg)`; 0 disables) are last
    generation's parsimony-best genomes with their RAW fitness: elitism
    places the same rows at [:E] of the next population, so the step
    bodies compare genomes exactly and skip the elite re-evaluation on a
    match. A zero-initialized cache never matches a well-formed genome
    (slot 0 is never EMPTY in either form), so the first generation
    always evaluates fully."""

    key: jax.Array
    op: jax.Array  # int32[P, N]
    arg: jax.Array  # int32[P, N]
    fitness: jax.Array  # float32[P] (of current population, minimize)
    best_op: jax.Array  # int32[N]
    best_arg: jax.Array  # int32[N]
    best_fitness: jax.Array  # float32[]
    generation: jax.Array  # int32[]
    cache_op: jax.Array  # int32[E, N]
    cache_arg: jax.Array  # int32[E, N]
    cache_fit: jax.Array  # float32[E]


def _dedup_kwargs(cfg: GPConfig, fn) -> dict:
    """The dedup kwargs to forward to a backend callable — {} when dedup
    is off, or when the callable predates the dedup contract (a
    user-registered backend without the kwargs keeps working; it simply
    never dedups)."""
    import inspect

    if cfg.dedup == "off":
        return {}
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return {}
    if "dedup" in params or any(p.kind == p.VAR_KEYWORD
                                for p in params.values()):
        return {"dedup": cfg.dedup, "dedup_cap": cfg.dedup_cap}
    return {}


def _eval_fitness(cfg: GPConfig, op, arg, X, y, weight, const_table):
    """Dispatch to the EvalBackend registered under `cfg.eval_impl`
    (repro.gp.backends — pallas fused kernel, jnp tiled reference, or any
    user-registered jittable backend). `weight` is the dataset-padding
    mask (f32[D], 0.0 on padded points) or None for unpadded data.
    `cfg.dedup`/`cfg.dedup_cap` ride along to backends that take them —
    the exact-tier subexpression dedup is a backend-internal, bitwise
    detail of how the population gets evaluated."""
    from repro.gp.backends import get_backend

    backend = get_backend(cfg.eval_impl)
    if not backend.jittable:
        raise ValueError(
            f"eval backend {backend.name!r} is host-only and cannot run inside "
            f"the jitted generation step; drive it through repro.gp.GPSession")
    return backend.fitness(op, arg, X, y, const_table, cfg.tree_spec, cfg.fitness,
                           weight=weight, data_tile=cfg.data_tile,
                           **_dedup_kwargs(cfg, backend.fitness))


def _eval_moments(cfg: GPConfig, op, arg, X, y, weight, const_table):
    """Phase 1 of the two-pass fitness protocol on the backend registered
    under `cfg.eval_impl`: f32[P, M] weighted moment partials for THIS
    shard's data. The mesh step `psum`s them across the data axis and
    finalizes with `FitnessKernel.reduce_moments` — how non-decomposable
    objectives (pearson, r2) run on any `MeshTopology`. Dedup engages
    per shard (each shard dedups its own population slice), bitwise like
    the single-device path."""
    from repro.gp.backends import get_backend

    backend = get_backend(cfg.eval_impl)
    if backend.moments is None:
        raise ValueError(
            f"eval backend {backend.name!r} exposes no moment pass and cannot "
            f"evaluate fitness under a data-sharded mesh")
    return backend.moments(op, arg, X, y, const_table, cfg.tree_spec, cfg.fitness,
                           weight=weight, data_tile=cfg.data_tile,
                           **_dedup_kwargs(cfg, backend.moments))


def init_state(cfg: GPConfig, key, seeds=None, feature_names=None) -> GPState:
    """Fresh state; `seeds` (expression strings) populate the first slots —
    Karoo's customized seed populations (paper §2.2). With
    `cfg.island.islands > 1` the state is island-batched (see GPState):
    every island draws its own decorrelated population and PRNG key via
    `fold_in(island_idx)`; seeds populate the first slots of EVERY island
    (the random filler still differs per island)."""
    k0, k1 = jax.random.split(key)
    I = cfg.island.islands

    def one_island(kk):
        if seeds:
            from repro.core.parse import seed_population

            return seed_population(seeds, cfg.tree_spec, cfg.pop_size, kk,
                                   feature_names)
        return generate_population(kk, cfg.pop_size, cfg.tree_spec)

    N = cfg.tree_spec.num_nodes
    E = cache_width(cfg)
    if I == 1:
        with host_span("fit.init_population"):
            op, arg = one_island(k1)
        return GPState(
            key=k0, op=op, arg=arg,
            fitness=jnp.full((cfg.pop_size,), jnp.inf, jnp.float32),
            best_op=jnp.zeros((N,), jnp.int32), best_arg=jnp.zeros((N,), jnp.int32),
            best_fitness=jnp.asarray(jnp.inf, jnp.float32),
            generation=jnp.asarray(0, jnp.int32),
            cache_op=jnp.zeros((E, N), jnp.int32),
            cache_arg=jnp.zeros((E, N), jnp.int32),
            cache_fit=jnp.full((E,), jnp.inf, jnp.float32),
        )
    if cfg.island.migrate_k > cfg.pop_size:
        raise ValueError(f"migrate_k {cfg.island.migrate_k} exceeds the "
                         f"per-island pop_size {cfg.pop_size}")
    with host_span("fit.init_population"):
        pairs = [one_island(jax.random.fold_in(k1, i)) for i in range(I)]
    keys = jnp.stack([jax.random.fold_in(k0, i) for i in range(I)])
    return GPState(
        key=keys,
        op=jnp.stack([p[0] for p in pairs]),
        arg=jnp.stack([p[1] for p in pairs]),
        fitness=jnp.full((I, cfg.pop_size), jnp.inf, jnp.float32),
        best_op=jnp.zeros((I, N), jnp.int32),
        best_arg=jnp.zeros((I, N), jnp.int32),
        best_fitness=jnp.full((I,), jnp.inf, jnp.float32),
        generation=jnp.asarray(0, jnp.int32),
        cache_op=jnp.zeros((I, E, N), jnp.int32),
        cache_arg=jnp.zeros((I, E, N), jnp.int32),
        cache_fit=jnp.full((I, E), jnp.inf, jnp.float32),
    )


def _semantic_hit(state_slice, cache_slice, cache_fit, probe):
    """Tier-2 (semantic) cache predicate: the candidate head rows produce
    BITWISE the same outputs as the cached rows on the probe batch
    (`probe(op, arg) -> f32[..., rows, Dp]`). Guarded on an all-finite
    cached fitness so the zero-initialized cache — whose all-EMPTY rows
    probe to 0.0, as would a legitimate x-minus-x elite — can never serve
    its +inf sentinel. Collision bound: a false hit needs the two
    genomes to agree on every one of the Dp probe points yet differ
    somewhere on the full dataset (see docs/genomes.md); the parity
    contract for dedup="semantic" is therefore tolerance-pinned, not
    bitwise."""
    (s_op, s_arg) = state_slice
    (c_op, c_arg) = cache_slice
    return (jnp.all(probe(s_op, s_arg) == probe(c_op, c_arg))
            & jnp.all(jnp.isfinite(cache_fit)))


def _cached_fitness(state: GPState, eval_rows, probe=None):
    """Evaluate `state`'s population, serving rows [:E] from the elite
    fitness cache when the cached genomes match exactly.

    `eval_rows(op, arg) -> f32[rows]` evaluates any row slice. E comes
    from the state's own cache shape, so the step body needs no extra
    static plumbing. Every eval path is row-independent, so splitting
    the population at E (and skipping the head on a hit — the cached
    value IS last generation's evaluation of the identical rows) is
    bitwise-identical to one full evaluation.

    `probe` (dedup="semantic" only) widens the hit predicate: a head
    whose PROBE outputs match the cache's also serves the cached fitness
    — recurring-but-rewritten elites hit across generations, at the cost
    of the documented probe-collision bound (`_semantic_hit`)."""
    E = state.cache_op.shape[0]
    if not E:
        return eval_rows(state.op, state.arg)
    hit = (jnp.all(state.op[:E] == state.cache_op)
           & jnp.all(state.arg[:E] == state.cache_arg))
    if probe is not None:
        hit = hit | _semantic_hit(
            (state.op[:E], state.arg[:E]),
            (state.cache_op, state.cache_arg), state.cache_fit, probe)
    tail = eval_rows(state.op[E:], state.arg[E:])
    head = jax.lax.cond(
        hit, lambda: state.cache_fit,
        lambda: eval_rows(state.op[:E], state.arg[:E]))
    return jnp.concatenate([head, tail])


def _new_cache(state: GPState, fitness, sel_fitness, E: int):
    """(cache_op, cache_arg, cache_fit) for the NEXT generation: the rows
    elitism will copy to [:E] — argsort on the selection fitness, exactly
    `next_generation`'s elite pick — paired with their RAW fitness. Rows
    are taken from the EVALUATED population (`state.op`), never from the
    bred output, so a migrant landing in [:E] can only MISS (re-evaluate),
    never match a stale fitness. Works per-island on [..., P] inputs."""
    best = jnp.argsort(sel_fitness, axis=-1)[..., :E]
    cache_op = jnp.take_along_axis(state.op, best[..., None], axis=-2)
    cache_arg = jnp.take_along_axis(state.arg, best[..., None], axis=-2)
    cache_fit = jnp.take_along_axis(fitness, best, axis=-1)
    return cache_op, cache_arg, cache_fit


_PROBE_COLS = 32  # semantic-tier fingerprint batch (first Dp data columns)


def _probe_fn(cfg: GPConfig, X, const_table):
    """Semantic-tier fingerprint closure, or None unless
    cfg.dedup == "semantic": evaluate rows on the first
    min(D, _PROBE_COLS) data columns — a fixed slice of the live
    dataset, so no extra state leaf rides GPState/checkpoints. Island
    inputs ([I, R, N]) flatten into one evaluator call."""
    if cfg.dedup != "semantic":
        return None
    from repro.core.eval import evaluate_population

    Dp = min(X.shape[1], _PROBE_COLS)
    Xp = jax.lax.slice_in_dim(X, 0, Dp, axis=1)

    def probe(o, a):
        N = o.shape[-1]
        flat = evaluate_population(o.reshape(-1, N), a.reshape(-1, N), Xp,
                                   const_table, cfg.tree_spec)
        return flat.reshape(*o.shape[:-1], Dp)

    return probe


def _step_body(cfg: GPConfig, state: GPState, X, y, weight) -> GPState:
    """One generation's computation — shared verbatim by the per-step jit
    (`evolve_step`) and the scanned block (`evolve_block`), so K scanned
    steps are bitwise-identical to K dispatched steps."""
    const_table = cfg.tree_spec.const_table()
    with jax.named_scope("gp.eval"):
        fitness = _cached_fitness(
            state,
            lambda o, a: _eval_fitness(cfg, o, a, X, y, weight, const_table),
            probe=_probe_fn(cfg, X, const_table))
    with jax.named_scope("gp.select_best"):
        # best tracked on RAW fitness; selection may add parsimony pressure
        i = jnp.argmin(fitness)
        improved = fitness[i] < state.best_fitness
        best_op = jnp.where(improved, state.op[i], state.best_op)
        best_arg = jnp.where(improved, state.arg[i], state.best_arg)
        best_fit = jnp.minimum(fitness[i], state.best_fitness)

        sel_fitness = fitness
        if cfg.parsimony:
            from repro.core.trees import tree_sizes

            sel_fitness = fitness + cfg.parsimony * tree_sizes(
                state.op).astype(jnp.float32)

        E = state.cache_op.shape[0]
        cache_op, cache_arg, cache_fit = (
            _new_cache(state, fitness, sel_fitness, E) if E
            else (state.cache_op, state.cache_arg, state.cache_fit))

    with jax.named_scope("gp.breed"):
        key, k_next = jax.random.split(state.key)
        new_op, new_arg = ev.next_generation(
            k_next, state.op, state.arg, sel_fitness, cfg.tree_spec, cfg.mix,
            cfg.tourn_size, cfg.elitism)
    return GPState(key, new_op, new_arg, fitness, best_op, best_arg, best_fit,
                   state.generation + 1, cache_op, cache_arg, cache_fit)


def _island_tables(cfg: GPConfig):
    """(probs f32[I, 4], tourn_max int, tourn int32[I], p_point f32[I]) —
    the heterogeneous-search parameter arrays one compiled program vmaps
    over (host numpy; they become constants in the jitted step)."""
    icfg = cfg.island
    tourn_max, tourn = icfg.tourn_table(cfg.tourn_size)
    return (icfg.prob_table(cfg.mix), tourn_max, tourn,
            icfg.point_rate_table())


def _island_step_body(cfg: GPConfig, state: GPState, X, y, weight) -> GPState:
    """One generation of the island-batched layout on a single device:
    evaluation runs over the flattened [I·P, N] population (one backend
    call — no vmap over the eval kernel), selection + breeding are
    vmapped over the island axis with per-island operator parameters,
    and migration routes elites across the island axis
    (islands.migrate_local). Shared verbatim by `evolve_step` and the
    scanned `evolve_block`, like the classic body."""
    from repro.core import islands as isl

    icfg = cfg.island
    I, P, N = state.op.shape
    const_table = cfg.tree_spec.const_table()

    def eval_rows(o, a):  # [I, R, N] -> [I, R], flattened into ONE backend call
        R = o.shape[1]
        return _eval_fitness(cfg, o.reshape(I * R, N), a.reshape(I * R, N),
                             X, y, weight, const_table).reshape(I, R)

    E = state.cache_op.shape[1]
    with jax.named_scope("gp.eval"):
        if E:
            # one hit predicate for ALL islands: a per-island cond would
            # lower to a select that evaluates both branches anyway. From
            # gen 2 every island hits every generation (migration only
            # writes the last migrate_k slots), so the all-or-nothing gate
            # costs nothing.
            hit = (jnp.all(state.op[:, :E] == state.cache_op)
                   & jnp.all(state.arg[:, :E] == state.cache_arg))
            probe = _probe_fn(cfg, X, const_table)
            if probe is not None:
                hit = hit | _semantic_hit(
                    (state.op[:, :E], state.arg[:, :E]),
                    (state.cache_op, state.cache_arg), state.cache_fit, probe)
            tail = eval_rows(state.op[:, E:], state.arg[:, E:])
            head = jax.lax.cond(
                hit, lambda: state.cache_fit,
                lambda: eval_rows(state.op[:, :E], state.arg[:, :E]))
            fitness = jnp.concatenate([head, tail], axis=1)
        else:
            fitness = eval_rows(state.op, state.arg)

    with jax.named_scope("gp.select_best"):
        # per-island champion tracking on RAW fitness
        i_best = jnp.argmin(fitness, axis=1)  # [I]
        rows = jnp.arange(I)
        cand_fit = fitness[rows, i_best]
        cand_op = state.op[rows, i_best]  # [I, N]
        cand_arg = state.arg[rows, i_best]
        improved = cand_fit < state.best_fitness
        best_op = jnp.where(improved[:, None], cand_op, state.best_op)
        best_arg = jnp.where(improved[:, None], cand_arg, state.best_arg)
        best_fit = jnp.minimum(cand_fit, state.best_fitness)

        sel_fitness = fitness
        if cfg.parsimony:
            from repro.core.trees import tree_sizes

            sizes = tree_sizes(state.op.reshape(I * P, N)).reshape(I, P)
            sel_fitness = fitness + cfg.parsimony * sizes.astype(jnp.float32)

        cache_op, cache_arg, cache_fit = (
            _new_cache(state, fitness, sel_fitness, E) if E
            else (state.cache_op, state.cache_arg, state.cache_fit))

    with jax.named_scope("gp.breed"):
        probs, tourn_max, tourn, p_point = _island_tables(cfg)
        breed = ev.make_island_breeder(cfg.tree_spec, tourn_max, cfg.elitism)
        keys, new_op, new_arg = jax.vmap(breed)(
            state.key, state.op, state.arg, sel_fitness, jnp.asarray(probs),
            jnp.asarray(tourn), jnp.asarray(p_point))

    if icfg.migrate_k and I > 1:
        with jax.named_scope("gp.migrate"):
            e_op, e_arg = isl.island_elites(state.op, state.arg, fitness,
                                            icfg.migrate_k)
            new_op, new_arg = isl.migrate_local(icfg, new_op, new_arg, e_op,
                                                e_arg, state.generation,
                                                cand_fit)
    return GPState(keys, new_op, new_arg, fitness, best_op, best_arg, best_fit,
                   state.generation + 1, cache_op, cache_arg, cache_fit)


def _step_body_any(cfg: GPConfig, state: GPState, X, y, weight) -> GPState:
    """Layout dispatch: the legacy single-population body (bitwise the
    pre-island path) or the island-batched body."""
    if cfg.island.islands > 1:
        return _island_step_body(cfg, state, X, y, weight)
    return _step_body(cfg, state, X, y, weight)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1,))
def evolve_step(cfg: GPConfig, state: GPState, X, y, weight=None) -> GPState:
    """One generation on a single device. X: [F, D] feature-major, y: [D];
    `weight` (f32[D] or None) masks dataset-padding points out of fitness.
    Island-batched states ([I, ...] leaves, cfg.island.islands > 1) run
    the island body; the classic layout runs the legacy body bitwise."""
    return _step_body_any(cfg, state, X, y, weight)


def _counter_row(cfg: GPConfig, state: GPState, done=None, *, mesh=False,
                 n_pods: int = 1):
    """int32[C] telemetry row for ONE scanned generation (columns:
    repro.obs.counters), computed from the PRE-step state — the same
    quantities the step body is about to consume, so the cache-hit gate
    CSEs with the step's own and the row costs a handful of scalar ops.
    Computed UNCONDITIONALLY: the compiled block program is identical
    whether anyone reads the counters, which is what pins telemetry
    on/off to bitwise-identical trajectories with zero recompiles.

    `done` is the block's freeze predicate for this step (None = the
    block can never freeze); a frozen step reports
    [0, 0, 1, 0, 0, 0, 0, 0] — its compute ran and was discarded. With
    `mesh=True` every quantity is replicated across shards (cache AND
    dedup columns are 0 there: the elite cache is host/single-device
    machinery, and re-running the dedup signature sort per shard purely
    for telemetry would double the mesh's plan cost; NODE_EVALS is 0
    there too: where a shard holds part of the population, summing it
    would add a collective to every generation) so the counter
    stream's out_spec is P(); `n_pods` sizes the classic mesh pod-ring
    migration count.

    The dedup columns (SUBTREE_EVALS_SAVED, UNIQUE_SUBTREES) recompute
    `eval.dedup_stats` on the PRE-step population — unconditional given
    cfg (static), so telemetry on/off stays bitwise with no recompile
    and no extra host sync, the PR-9 pins. They are 0 when
    cfg.dedup == "off", on non-postfix genomes, and on overflow (the
    eval path then ran the plain interpreter)."""
    I = cfg.island.islands
    island = I > 1
    zero = jnp.asarray(0, jnp.int32)
    E = 0 if mesh else state.cache_op.shape[1 if island else 0]
    if not E:
        hit, queries = zero, zero
    elif island:
        hit = (jnp.all(state.op[:, :E] == state.cache_op)
               & jnp.all(state.arg[:, :E] == state.cache_arg)).astype(jnp.int32)
        queries = jnp.asarray(1, jnp.int32)
    else:
        hit = (jnp.all(state.op[:E] == state.cache_op)
               & jnp.all(state.arg[:E] == state.cache_arg)).astype(jnp.int32)
        queries = jnp.asarray(1, jnp.int32)
    # tree evaluations this generation (cache-served rows excluded);
    # the host multiplies by the dataset row count for trees·rows
    evals = jnp.asarray(I * cfg.pop_size, jnp.int32) - hit * (I * E)
    if mesh:
        nodes = zero
    else:
        active = (state.op != prim.EMPTY).astype(jnp.int32)
        nodes = active.sum() - hit * active[..., :E, :].sum()
    if island and cfg.island.migrate_k:
        due = ((state.generation % cfg.island.migrate_every)
               == (cfg.island.migrate_every - 1))
        migrations = jnp.where(due, I, 0).astype(jnp.int32)
    elif (not island) and mesh and n_pods > 1:
        due = ((state.generation % cfg.migrate_every)
               == (cfg.migrate_every - 1))
        migrations = jnp.where(due, n_pods, 0).astype(jnp.int32)
    else:
        migrations = zero
    if mesh or cfg.dedup == "off" or cfg.tree_spec.genome != "postfix":
        saved = uniq = zero
    else:
        from repro.core.eval import dedup_stats, resolve_dedup_cap

        N = cfg.tree_spec.num_nodes
        o = state.op.reshape(-1, N)
        a = state.arg.reshape(-1, N)
        cap = resolve_dedup_cap(cfg.dedup_cap, o.shape[0], N)
        uniq, saved = dedup_stats(o, a, cfg.tree_spec, cap)
    row = jnp.stack([hit, queries, zero, migrations, evals, saved, uniq,
                     nodes])
    if done is None:
        return row
    return jnp.where(done, jnp.asarray([0, 0, 1, 0, 0, 0, 0, 0], jnp.int32),
                     row)


def _block_done(cfg: GPConfig, state: GPState, i, limit):
    """Branch-free freeze predicate for step `i` of a block: True once
    `best_fitness` has reached `cfg.stop_fitness` (on-device early stop;
    the min across islands for island-batched state) or `i` has reached
    the dynamic `limit` (a traced step budget that lets ONE compiled
    fixed-length block program serve ragged block boundaries —
    checkpoint/callback phases, final partial blocks — without
    recompiling per distinct length)."""
    done = jnp.asarray(False)
    if cfg.stop_fitness is not None:
        best = state.best_fitness
        if best.ndim:  # island-batched: any island reaching the bar stops
            best = best.min()
        done = best <= cfg.stop_fitness
    if limit is not None:
        done = done | (i >= limit)
    return done


def _freeze(done, prev: GPState, new: GPState) -> GPState:
    """Carry `prev` through unchanged (PRNG key and generation counter
    included) when `done` — frozen steps are no-ops, so the host reads
    how many generations actually ran off `state.generation`."""
    return jax.tree.map(lambda p, n: jnp.where(done, p, n), prev, new)


@partial(jax.jit, static_argnames=("cfg", "n_steps"), donate_argnums=(1,))
def evolve_block(cfg: GPConfig, state: GPState, X, y, weight=None, limit=None, *,
                 n_steps: int = 1):
    """Run up to `n_steps` generations in ONE device dispatch via `lax.scan`.

    Returns (state, history, counters) where history is the
    per-generation `best_fitness` stream — f32[n_steps] for the classic
    layout, f32[n_steps, I] (one column per island) for island-batched
    state — and counters is the int32[n_steps, C] telemetry stream
    (repro.obs.counters: cache hits/queries, frozen steps, migrations,
    tree evals), so the block's metrics ride back with the state instead
    of forcing a host sync per generation. Steps freeze into no-ops once
    `cfg.stop_fitness` is reached or the step index hits `limit`
    (dynamic int32; None = run all `n_steps`), so one compiled program
    covers every block length ≤ n_steps. The freeze is a branch-free
    select, not a skip: frozen steps still execute the generation's
    compute and discard it (a frozen step's migrations are discarded
    with it) — callers bound the waste by choosing n_steps (GPSession
    caps it at the configured period, or _STOP_CHECK_SPAN when only
    stop_fitness is armed)."""

    can_freeze = cfg.stop_fitness is not None or limit is not None

    def body(s, i):
        nxt = _step_body_any(cfg, s, X, y, weight)
        done = _block_done(cfg, s, i, limit)
        with jax.named_scope("gp.telemetry"):
            row = _counter_row(cfg, s, done if can_freeze else None)
        if can_freeze:
            nxt = _freeze(done, s, nxt)
        return nxt, (nxt.best_fitness, row)

    state, (history, counters) = jax.lax.scan(body, state, jnp.arange(n_steps))
    return state, history, counters


def run(cfg: GPConfig, X, y, key=None, generations: int | None = None,
        callback=None, seeds=None, feature_names=None) -> GPState:
    """DEPRECATED — thin forwarder to :class:`repro.gp.GPSession`, kept so
    pre-session callers don't break. X is feature-major [F, D] (the old
    contract); the session's own `fit` takes row-major data."""
    warnings.warn(
        "repro.core.run is deprecated; use repro.gp.GPSession "
        "(session = GPSession(cfg); session.fit(X_rows, y)) instead",
        DeprecationWarning, stacklevel=2)
    from repro.gp import GPSession

    sess = GPSession(cfg, feature_names=feature_names, callback=callback)
    sess.ingest(X, y, layout="features")
    sess.init(key=key, seeds=seeds)
    sess.evolve(generations)
    return sess.state


# --- multi-tenant step (repro.service) ----------------------------------------


class TenantParams(NamedTuple):
    """Per-slot search/termination parameters of a multi-tenant batch.
    Every leaf is [I]-leading and TRACED — admission and eviction at
    block boundaries rebind values on the same compiled program, so a
    long-lived service never recompiles as jobs come and go. The only
    static knobs of a tenant block are the shared shapes (`TreeSpec`,
    pop_size, data capacity), the kernel tuple `lax.switch` branches
    over, the tournament DRAW size (the random draw's shape — per-slot
    `tourn` masks down from it, `core/evolve.tournament`) and elitism.

        probs       f32[I, 4]   operator-mix probabilities per slot
        tourn       int32[I]    active tournament size (≤ the draw size)
        point_rate  f32[I]      point-mutation rate
        kernel_id   int32[I]    index into the block's static kernel tuple
        n_classes   f32[I]      classify arity (unused by other kernels)
        precision   f32[I]      match tolerance (unused by other kernels)
        stop        f32[I]      stop_fitness; -inf disables early stop
        budget      int32[I]    generation budget; 0 marks an EMPTY slot
    """

    probs: jax.Array
    tourn: jax.Array
    point_rate: jax.Array
    kernel_id: jax.Array
    n_classes: jax.Array
    precision: jax.Array
    stop: jax.Array
    budget: jax.Array


class TenantState(NamedTuple):
    """Island-batched engine state for a multi-tenant batch: the GPState
    island layout with the shared lockstep `generation` scalar replaced
    by per-slot `gens_done` counters — tenants start, stop and swap out
    independently, so no scalar is shared across slots and
    `islands.take_island`/`splice_island` move a whole job's evolution
    state in ONE slice.

        key           uint32[I, 2]    per-slot PRNG (a solo run's stream)
        op/arg        int32[I, P, N]
        fitness       f32[I, P]
        best_op/arg   int32[I, N]
        best_fitness  f32[I]
        gens_done     int32[I]
        cache_op/arg  int32[I, E, N]  per-slot elite fitness cache
        cache_fit     f32[I, E]       (same contract as GPState's)
    """

    key: jax.Array
    op: jax.Array
    arg: jax.Array
    fitness: jax.Array
    best_op: jax.Array
    best_arg: jax.Array
    best_fitness: jax.Array
    gens_done: jax.Array
    cache_op: jax.Array
    cache_arg: jax.Array
    cache_fit: jax.Array


def tenant_active(state: TenantState, params: TenantParams):
    """bool[I]: which slots still evolve — budget not exhausted AND the
    early-stop bar (params.stop, -inf = disabled) not reached. Works on
    device arrays and host numpy alike."""
    return (state.gens_done < params.budget) & jnp.logical_not(
        state.best_fitness <= params.stop)


def _tenant_cache_width(elitism: int, pop_size: int, elite_cache: bool) -> int:
    """cache_width for the tenant batch (elitism is the block's shared
    static; the same guard as the session engine's)."""
    return elitism if (elite_cache and 0 < elitism < pop_size) else 0


def init_tenant_slot(key, pop_size: int, spec: TreeSpec, elitism: int = 1,
                     elite_cache: bool = True) -> TenantState:
    """ONE job's fresh sub-state (un-batched leaves, ready for
    `islands.splice_island`). Keyed exactly like `init_state` with
    islands == 1 — split once, population from the second half, slot key
    from the first — so a packed job replays a solo session's PRNG
    stream bit-for-bit. `elitism`/`elite_cache` size the slot's elite
    fitness cache and must match the block's."""
    k0, k1 = jax.random.split(key)
    op, arg = generate_population(k1, pop_size, spec)
    N = spec.num_nodes
    E = _tenant_cache_width(elitism, pop_size, elite_cache)
    return TenantState(
        key=k0, op=op, arg=arg,
        fitness=jnp.full((pop_size,), jnp.inf, jnp.float32),
        best_op=jnp.zeros((N,), jnp.int32), best_arg=jnp.zeros((N,), jnp.int32),
        best_fitness=jnp.asarray(jnp.inf, jnp.float32),
        gens_done=jnp.asarray(0, jnp.int32),
        cache_op=jnp.zeros((E, N), jnp.int32),
        cache_arg=jnp.zeros((E, N), jnp.int32),
        cache_fit=jnp.full((E,), jnp.inf, jnp.float32),
    )


def empty_tenant_state(islands: int, pop_size: int, spec: TreeSpec,
                       elitism: int = 1,
                       elite_cache: bool = True) -> TenantState:
    """An all-empty batch (pair with budget-0 TenantParams rows: empty
    slots never advance; their compute is frozen out)."""
    I, P, N = islands, pop_size, spec.num_nodes
    E = _tenant_cache_width(elitism, pop_size, elite_cache)
    return TenantState(
        key=jnp.zeros((I, 2), jnp.uint32),
        op=jnp.zeros((I, P, N), jnp.int32), arg=jnp.zeros((I, P, N), jnp.int32),
        fitness=jnp.full((I, P), jnp.inf, jnp.float32),
        best_op=jnp.zeros((I, N), jnp.int32), best_arg=jnp.zeros((I, N), jnp.int32),
        best_fitness=jnp.full((I,), jnp.inf, jnp.float32),
        gens_done=jnp.zeros((I,), jnp.int32),
        cache_op=jnp.zeros((I, E, N), jnp.int32),
        cache_arg=jnp.zeros((I, E, N), jnp.int32),
        cache_fit=jnp.full((I, E), jnp.inf, jnp.float32),
    )


def _switch_fitness(kernels: tuple, preds, y, w, kernel_id, n_classes, precision):
    """f32[P] fitness of one slot's predictions under its TRACED kernel
    choice: `lax.switch` over the block's static kernel tuple, each
    branch the registered kernel's whole-dataset `partial_fitness` fed a
    duck-typed spec whose n_classes/precision are traced f32 — the
    kernels only consume them inside jnp ops, so one compiled program
    serves every per-slot value."""
    import types

    duck = types.SimpleNamespace(n_classes=n_classes, precision=precision)
    branches = [partial(lambda kern, p, yy, ww: kern.partial_fitness(p, yy, ww, duck),
                        fit.get_kernel(name)) for name in kernels]
    return jax.lax.switch(kernel_id, branches, preds, y, w)


def _tenant_slot_step(spec: TreeSpec, kernels: tuple, tourn_draw: int,
                      elitism: int, sub: TenantState, Xi, yi, wi,
                      p: TenantParams, dedup: str = "off",
                      dedup_cap: int = 0) -> TenantState:
    """One generation of ONE slot — deliberately the solo `_step_body`
    re-derived on un-batched leaves (evaluate → whole-dataset fitness →
    champion → split/breed → freeze), because the tenant batch runs it
    under `lax.map`, whose scan body traces this function UN-vmapped:
    the compiled reductions are the ones a solo `islands=1` session
    runs, so packed-vs-solo parity is bitwise, not just approximate
    (vmap would re-lower the fitness reductions batched and change f32
    rounding). The freeze predicate is computed on the PRE-step state,
    matching `_block_done`; a frozen (done or empty) slot's step
    computes and discards, like every freeze in this engine."""
    from repro.core.eval import (evaluate_population,
                                 evaluate_population_dedup, resolve_dedup_cap)

    active = tenant_active(sub, p)
    const_table = spec.const_table()
    use_dedup = dedup != "off" and spec.genome == "postfix"

    def eval_rows(o, a):  # f32[rows]; row-independent, so slicing is exact
        if use_dedup:
            # each slice dedups independently — bitwise equal to the
            # plain interpreter on the same rows, so packed-vs-solo and
            # dedup-on-vs-off parity both stay bitwise
            cap = resolve_dedup_cap(dedup_cap, o.shape[0], o.shape[1])
            preds = evaluate_population_dedup(o, a, Xi, const_table, spec, cap)
        else:
            preds = evaluate_population(o, a, Xi, const_table, spec)
        return _switch_fitness(kernels, preds, yi, wi, p.kernel_id,
                               p.n_classes, p.precision)

    E = sub.cache_op.shape[0]
    with jax.named_scope("gp.eval"):
        if E:
            hit = (jnp.all(sub.op[:E] == sub.cache_op)
                   & jnp.all(sub.arg[:E] == sub.cache_arg))
            tail = eval_rows(sub.op[E:], sub.arg[E:])
            head = jax.lax.cond(hit, lambda: sub.cache_fit,
                                lambda: eval_rows(sub.op[:E], sub.arg[:E]))
            fitness = jnp.concatenate([head, tail])
        else:
            fitness = eval_rows(sub.op, sub.arg)
    with jax.named_scope("gp.select_best"):
        i = jnp.argmin(fitness)
        improved = fitness[i] < sub.best_fitness
        best_op = jnp.where(improved, sub.op[i], sub.best_op)
        best_arg = jnp.where(improved, sub.arg[i], sub.best_arg)
        best_fit = jnp.minimum(fitness[i], sub.best_fitness)

        if E:
            # the tenant breeder selects elites on RAW fitness, so the next
            # cache is argsort(fitness)[:E] of the evaluated population
            best = jnp.argsort(fitness)[:E]
            cache_op, cache_arg = sub.op[best], sub.arg[best]
            cache_fit = fitness[best]
        else:
            cache_op, cache_arg, cache_fit = (sub.cache_op, sub.cache_arg,
                                              sub.cache_fit)

    with jax.named_scope("gp.breed"):
        breed = ev.make_island_breeder(spec, tourn_draw, elitism)
        key, new_op, new_arg = breed(sub.key, sub.op, sub.arg, fitness,
                                     p.probs, p.tourn, p.point_rate)
    nxt = TenantState(key, new_op, new_arg, fitness, best_op, best_arg,
                      best_fit, sub.gens_done + 1, cache_op, cache_arg,
                      cache_fit)
    return jax.tree.map(lambda prev, new: jnp.where(active, new, prev), sub, nxt)


def tenant_step(spec: TreeSpec, kernels: tuple, tourn_draw: int, elitism: int,
                state: TenantState, X, y, weight,
                params: TenantParams, dedup: str = "off",
                dedup_cap: int = 0) -> TenantState:
    """One generation of the whole batch: `lax.map` of the slot step over
    the island axis. X f32[I, F, Dc], y f32[I, Dc], weight f32[I, Dc] —
    every slot carries its OWN (padded, zero-weight-masked) dataset
    slice, so heterogeneous jobs never evaluate each other's data.
    `dedup`/`dedup_cap` (static) engage the exact-tier subexpression
    dedup inside each slot's evaluation — bitwise-identical results."""
    return jax.lax.map(
        lambda t: _tenant_slot_step(spec, kernels, tourn_draw, elitism, *t,
                                    dedup=dedup, dedup_cap=dedup_cap),
        (state, X, y, weight, params))


def _tenant_counter_row(state: TenantState, params: TenantParams):
    """int32[C] telemetry row for one tenant-batch generation, from the
    PRE-step state (columns: repro.obs.counters). Cache hits/queries
    count per ACTIVE slot (the per-slot gates the slot steps are about
    to take); FROZEN counts inactive slots — finished, early-stopped,
    or empty — whose compute runs and is discarded this generation;
    TREE_EVALS sums each active slot's non-cache-served rows and
    NODE_EVALS their active genome slots. Computed
    unconditionally, like every counter row, so the service's
    no-recompile guarantee is untouched. The dedup columns are 0 here,
    like the cache columns on a mesh: slot steps dedup their own row
    slices under `lax.map`, and re-running the signature sort per slot
    purely for telemetry would double the batch's plan cost."""
    E = state.cache_op.shape[1]
    P_ = state.op.shape[1]
    a32 = tenant_active(state, params).astype(jnp.int32)
    if E:
        h32 = (jnp.all(state.op[:, :E] == state.cache_op, axis=(1, 2))
               & jnp.all(state.arg[:, :E] == state.cache_arg,
                         axis=(1, 2))).astype(jnp.int32)
        hits = (h32 * a32).sum()
        queries = a32.sum()
    else:
        h32 = jnp.zeros_like(a32)
        hits = queries = jnp.asarray(0, jnp.int32)
    frozen = (1 - a32).sum()
    evals = (a32 * (P_ - h32 * E)).sum()
    active = (state.op != prim.EMPTY).astype(jnp.int32)
    nodes = (a32 * (active.sum((1, 2))
                    - h32 * active[:, :E].sum((1, 2)))).sum()
    zero = jnp.asarray(0, jnp.int32)
    return jnp.stack([hits, queries, frozen, zero, evals, zero, zero, nodes])


def build_tenant_block(spec: TreeSpec, kernels: tuple, tourn_draw: int,
                       elitism: int, n_steps: int, *, dedup: str = "off",
                       dedup_cap: int = 0):
    """The service's ONE compiled program: block(state, X, y, weight,
    params) -> (state, history f32[n_steps, I], counters
    int32[n_steps, C]) scanning `tenant_step` `n_steps` generations per
    dispatch — the counter stream (repro.obs.counters) rides back with
    the same dispatch. Everything per-job is a traced operand
    (TenantParams + the slot data buffers), so the scheduler splices
    jobs in and out between dispatches without recompiling. Kernel
    names are canonicalized (aliases collapse) at build time; jit it
    with donate_argnums=(0,) — the caller owns that."""
    kernels = tuple(fit.get_kernel(k).name for k in kernels)
    for name in kernels:
        if fit.get_kernel(name).partial_fitness is None:
            raise ValueError(f"fitness kernel {name!r} has no whole-dataset "
                             f"partial_fitness; the tenant block cannot "
                             f"switch over it")

    def block(state: TenantState, X, y, weight, params: TenantParams):
        def body(s, _):
            with jax.named_scope("gp.telemetry"):
                row = _tenant_counter_row(s, params)
            nxt = tenant_step(spec, kernels, tourn_draw, elitism, s, X, y,
                              weight, params, dedup=dedup,
                              dedup_cap=dedup_cap)
            return nxt, (nxt.best_fitness, row)

        st, (hist, counters) = jax.lax.scan(body, state, None,
                                            length=n_steps)
        return st, hist, counters

    return block


# --- mesh-sharded step --------------------------------------------------------


def _merge_moments_on_mesh(kern, fit_spec, partial_m, y, weight, data_axis,
                           n_data: int):
    """Complete phase 1 across the mesh data axis WITHOUT finalizing:
    per-shard moment partials f32[P*, M] → globally merged moments
    f32[P*, M], replicated on every data shard. `_reduce_moments_on_mesh`
    finalizes for the generation step; the streaming fold
    (`build_stream_fold`) instead merges each chunk's result into a
    carried accumulator and finalizes once at end of stream.

    Three lowerings, picked by the kernel's protocol surface:

      plain sum          `lax.psum` of the full [P*, M] payload — the
                         classic path, bitwise what it always was for
                         decomposable kernels.
      + y-hoisting       the tree-independent columns (`y_moment_idx`,
                         identical on every row) ride ONCE per shard:
                         psum [P*, Mt] + [My] instead of [P*, M] — for
                         pearson that is ~half the reduction bytes.
      pairwise combine   kernels with a non-additive merge (centered
                         moments + Chan combine): `all_gather` the
                         per-shard partials and fold with
                         `combine_moments` — n_data is small and the
                         payload already shrank via hoisting.
    """
    if kern.combine_moments is None:
        if not kern.y_moment_idx:
            return jax.lax.psum(partial_m, data_axis)
        t_idx = jnp.asarray(kern.tree_moment_idx)
        tree_m = jax.lax.psum(partial_m[..., t_idx], data_axis)
        y_m = jax.lax.psum(kern.y_moments(y, weight, fit_spec), data_axis)
        return fit.scatter_tree_y(kern, tree_m, y_m)
    if kern.y_moment_idx:
        t_idx = jnp.asarray(kern.tree_moment_idx)
        # row 0's y-columns == every row's (tree-independent by contract)
        tree_parts = jax.lax.all_gather(partial_m[..., t_idx], data_axis)
        y_parts = jax.lax.all_gather(
            partial_m[0, jnp.asarray(kern.y_moment_idx)], data_axis)
        parts = [fit.scatter_tree_y(kern, tree_parts[s], y_parts[s])
                 for s in range(n_data)]
    else:
        gathered = jax.lax.all_gather(partial_m, data_axis)
        parts = [gathered[s] for s in range(n_data)]
    return fit.fold_moment_partials(kern, parts, fit_spec)


def _reduce_moments_on_mesh(kern, fit_spec, partial_m, y, weight, data_axis,
                            n_data: int):
    """Complete phase 1 across the mesh data axis and finalize: per-shard
    moment partials f32[P*, M] → fitness f32[P*] (replicated). See
    `_merge_moments_on_mesh` for the three reduction lowerings."""
    return kern.reduce_moments(
        _merge_moments_on_mesh(kern, fit_spec, partial_m, y, weight,
                               data_axis, n_data), fit_spec)


def _sharded_step_builder(cfg: GPConfig, mesh, *, data_axis="data",
                          model_axis="model", pod_axis: str | None = None):
    """Per-shard generation-step body + its PartitionSpecs — the common
    core of `sharded_evolve_step` (one step per dispatch) and
    `sharded_evolve_block` (K steps per dispatch via an in-shard_map
    scan). Returns (step, state_specs, data_spec, y_spec, w_spec)."""
    from repro.core.islands import migrate

    kern = fit.get_kernel(cfg.fitness.kernel)
    if kern.moments is None:
        raise ValueError(
            f"fitness kernel {kern.name!r} defines no moment pass "
            f"(moments/reduce_moments), so nothing can be psum-reduced across "
            f"the {data_axis!r} axis; register it through the two-pass protocol "
            f"(see docs/fitness-kernels.md) or run single-device")

    pod_dims = (pod_axis,) if pod_axis else ()
    n_shards = mesh.shape[model_axis]
    for a in pod_dims:
        n_shards *= mesh.shape[a]
    if cfg.pop_size % n_shards:
        raise ValueError(f"pop_size {cfg.pop_size} % population shards {n_shards} != 0")
    n_model = mesh.shape[model_axis]

    pop_spec = P((*pod_dims, model_axis))
    data_spec = P(None, data_axis)  # X is [F, D]
    y_spec = P(data_axis)
    w_spec = P(data_axis)  # padding mask rides the same axis as y
    state_specs = GPState(
        key=P(), op=pop_spec, arg=pop_spec, fitness=pop_spec,
        best_op=P(), best_arg=P(), best_fitness=P(), generation=P(),
        # the elite cache is host/single-device machinery: mesh steps carry
        # it through replicated and untouched (they re-seed elites via the
        # rank-0 champion row, not the [:E] convention the cache keys on)
        cache_op=P(), cache_arg=P(), cache_fit=P(),
    )

    n_data = mesh.shape[data_axis]

    def step(state: GPState, X, y, weight) -> GPState:
        const_table = cfg.tree_spec.const_table()
        # --- evaluate, two passes: local pop shard x local data shard
        # emits weighted moments; the data-axis reduction completes
        # phase 1 (psum, hoisted psum, or combine-fold — see
        # _reduce_moments_on_mesh) and reduce_moments finalizes — for
        # decomposable kernels M == 1 and this degenerates to the
        # classic psum-of-partials
        with jax.named_scope("gp.eval"):
            partial_m = _eval_moments(cfg, state.op, state.arg, X, y, weight,
                                      const_table)
            fitness_local = _reduce_moments_on_mesh(
                kern, cfg.fitness, partial_m, y, weight, data_axis, n_data)
        with jax.named_scope("gp.select_best"):
            # --- selection pool = this pod's population: tiny all_gather
            fitness_g = jax.lax.all_gather(fitness_local, model_axis,
                                           tiled=True)
            op_g = jax.lax.all_gather(state.op, model_axis, tiled=True)
            arg_g = jax.lax.all_gather(state.arg, model_axis, tiled=True)

            # --- pod-local best, then global best across pods (replicated)
            i = jnp.argmin(fitness_g)
            cand_fit, cand_op, cand_arg = fitness_g[i], op_g[i], arg_g[i]
            if pod_axis:
                pods_fit = jax.lax.all_gather(cand_fit, pod_axis)  # [n_pods]
                pods_op = jax.lax.all_gather(cand_op, pod_axis)  # [n_pods, N]
                pods_arg = jax.lax.all_gather(cand_arg, pod_axis)
                j = jnp.argmin(pods_fit)
                cand_fit, cand_op, cand_arg = (pods_fit[j], pods_op[j],
                                               pods_arg[j])
            improved = cand_fit < state.best_fitness
            best_op = jnp.where(improved, cand_op, state.best_op)
            best_arg = jnp.where(improved, cand_arg, state.best_arg)
            best_fit = jnp.minimum(cand_fit, state.best_fitness)

        with jax.named_scope("gp.breed"):
            # --- offspring for this shard's slice only (decorrelated RNG)
            rank = jax.lax.axis_index(model_axis)
            key = state.key
            if pod_axis:
                key = jax.random.fold_in(key, jax.lax.axis_index(pod_axis))
            key = jax.random.fold_in(key, state.generation)
            k_rank = jax.random.fold_in(key, rank)
            n_local = cfg.pop_size // n_shards
            new_op, new_arg = ev.next_generation(
                k_rank, op_g, arg_g, fitness_g, cfg.tree_spec, cfg.mix,
                cfg.tourn_size, elitism=0, n_out=n_local)
            # elitism: rank 0 of each pod re-seeds the pod's own champion
            if cfg.elitism:
                keep = rank == 0
                new_op = new_op.at[0].set(jnp.where(keep, op_g[i], new_op[0]))
                new_arg = new_arg.at[0].set(
                    jnp.where(keep, arg_g[i], new_arg[0]))
        if pod_axis:
            with jax.named_scope("gp.migrate"):
                order = jnp.argsort(fitness_g)[:cfg.migrate_k]
                new_op, new_arg = migrate(
                    cfg, new_op, new_arg, op_g[order], arg_g[order],
                    state.generation, pod_axis,
                    is_receiver=rank == n_model - 1)
        return GPState(state.key, new_op, new_arg, fitness_local, best_op, best_arg,
                       best_fit, state.generation + 1,
                       state.cache_op, state.cache_arg, state.cache_fit)

    return step, state_specs, data_spec, y_spec, w_spec


def _sharded_island_step_builder(cfg: GPConfig, mesh, *, data_axis="data",
                                 model_axis="model", pod_axis: str | None = None):
    """Per-shard generation step for the ISLAND-BATCHED layout
    (cfg.island.islands = I > 1): the global state is `op int32[I, P, N]`
    with the island axis sharded over the pod axis (I_local = I / n_pods
    islands per pod) and each island's population sharded over the model
    axis — pods × in-device islands from one builder. Evaluation flattens
    the local islands into one backend call; selection + breeding vmap
    over the island axis with per-island operator parameters; migration
    is the composed lowering (in-device roll + pod-boundary ppermute,
    islands.migrate_sharded). Returns the same tuple contract as the
    legacy builder."""
    from repro.core import islands as isl

    icfg = cfg.island
    I = icfg.islands
    kern = fit.get_kernel(cfg.fitness.kernel)
    if kern.moments is None:
        raise ValueError(
            f"fitness kernel {kern.name!r} defines no moment pass "
            f"(moments/reduce_moments), so nothing can be reduced across "
            f"the {data_axis!r} axis; register it through the two-pass protocol "
            f"(see docs/fitness-kernels.md) or run single-device")

    n_pods = mesh.shape[pod_axis] if pod_axis else 1
    if I % n_pods:
        raise ValueError(f"islands {I} % pod axis {n_pods} != 0 — the pod "
                         f"axis shards whole islands")
    n_model = mesh.shape[model_axis]
    if cfg.pop_size % n_model:
        raise ValueError(f"per-island pop_size {cfg.pop_size} % model axis "
                         f"{n_model} != 0")
    n_local = cfg.pop_size // n_model
    if icfg.migrate_k > n_local:
        raise ValueError(f"migrate_k {icfg.migrate_k} exceeds the last model "
                         f"rank's {n_local}-tree slice that receives migrants")
    n_data = mesh.shape[data_axis]

    pod = pod_axis  # None → replicated island axis (in-device islands only)
    pop_spec = P(pod, model_axis, None)
    data_spec = P(None, data_axis)  # X is [F, D]
    y_spec = P(data_axis)
    w_spec = P(data_axis)
    state_specs = GPState(
        key=P(pod, None), op=pop_spec, arg=pop_spec,
        fitness=P(pod, model_axis),
        best_op=P(pod, None), best_arg=P(pod, None),
        best_fitness=P(pod), generation=P(),
        # cache rides the island (pod) axis, untouched by the mesh step
        cache_op=P(pod, None, None), cache_arg=P(pod, None, None),
        cache_fit=P(pod, None),
    )
    probs_t, tourn_max, tourn_t, pp_t = _island_tables(cfg)

    def step(state: GPState, X, y, weight) -> GPState:
        const_table = cfg.tree_spec.const_table()
        Il, Pl, N = state.op.shape  # per-shard: I_local, pop/model, nodes
        with jax.named_scope("gp.eval"):
            partial_m = _eval_moments(cfg, state.op.reshape(Il * Pl, N),
                                      state.arg.reshape(Il * Pl, N), X, y,
                                      weight, const_table)
            fitness_local = _reduce_moments_on_mesh(
                kern, cfg.fitness, partial_m, y, weight, data_axis,
                n_data).reshape(Il, Pl)
        with jax.named_scope("gp.select_best"):
            # --- selection pool = each island's own population: tiny gathers
            fitness_g = jax.lax.all_gather(fitness_local, model_axis, axis=1,
                                           tiled=True)  # [Il, P]
            op_g = jax.lax.all_gather(state.op, model_axis, axis=1,
                                      tiled=True)
            arg_g = jax.lax.all_gather(state.arg, model_axis, axis=1,
                                       tiled=True)

            # --- per-island champion (each pod owns its islands' streams)
            i = jnp.argmin(fitness_g, axis=1)  # [Il]
            rows = jnp.arange(Il)
            cand_fit, cand_op, cand_arg = (fitness_g[rows, i], op_g[rows, i],
                                           arg_g[rows, i])
            improved = cand_fit < state.best_fitness
            best_op = jnp.where(improved[:, None], cand_op, state.best_op)
            best_arg = jnp.where(improved[:, None], cand_arg, state.best_arg)
            best_fit = jnp.minimum(cand_fit, state.best_fitness)

            sel_fitness = fitness_g
            if cfg.parsimony:
                from repro.core.trees import tree_sizes

                sizes = tree_sizes(op_g.reshape(Il * cfg.pop_size, N))
                sel_fitness = fitness_g + cfg.parsimony * sizes.reshape(
                    Il, cfg.pop_size).astype(jnp.float32)

        with jax.named_scope("gp.breed"):
            # --- offspring for this shard's slice (decorrelated per island
            # via the per-island key, per rank via fold_in); per-island
            # search parameters are the pod's slice of the global tables
            rank = jax.lax.axis_index(model_axis)
            start = (jax.lax.axis_index(pod) if pod else 0) * Il
            probs_l = jax.lax.dynamic_slice_in_dim(jnp.asarray(probs_t),
                                                   start, Il, 0)
            tourn_l = jax.lax.dynamic_slice_in_dim(jnp.asarray(tourn_t),
                                                   start, Il, 0)
            pp_l = jax.lax.dynamic_slice_in_dim(jnp.asarray(pp_t), start,
                                                Il, 0)

            breed = ev.make_island_breeder(cfg.tree_spec, tourn_max,
                                           elitism=0, n_out=n_local,
                                           fold=rank)
            keys, new_op, new_arg = jax.vmap(breed)(
                state.key, op_g, arg_g, sel_fitness, probs_l, tourn_l, pp_l)
            # elitism: rank 0's slice re-seeds each island's own champion
            if cfg.elitism:
                keep = rank == 0
                new_op = new_op.at[:, 0].set(
                    jnp.where(keep, cand_op, new_op[:, 0]))
                new_arg = new_arg.at[:, 0].set(
                    jnp.where(keep, cand_arg, new_arg[:, 0]))
        if icfg.migrate_k and I > 1:
            with jax.named_scope("gp.migrate"):
                e_op, e_arg = isl.island_elites(op_g, arg_g, fitness_g,
                                                icfg.migrate_k)
                new_op, new_arg = isl.migrate_sharded(
                    icfg, new_op, new_arg, e_op, e_arg, state.generation,
                    cand_fit, pod, is_receiver=rank == n_model - 1)
        return GPState(keys, new_op, new_arg, fitness_local, best_op, best_arg,
                       best_fit, state.generation + 1,
                       state.cache_op, state.cache_arg, state.cache_fit)

    return step, state_specs, data_spec, y_spec, w_spec


def _pick_step_builder(cfg: GPConfig):
    return (_sharded_island_step_builder if cfg.island.islands > 1
            else _sharded_step_builder)


def sharded_evolve_step(cfg: GPConfig, mesh, *, data_axis="data", model_axis="model",
                        pod_axis: str | None = None):
    """Build a shard_map'd generation step for `mesh`.

    Shardings: X, y, weight on (data,). Classic layout (islands == 1):
    the population's leading axis is on (pod, model) — the pod slices
    are the islands, the model slices are a pod's parallel evaluation
    shards — and best_* is replicated (global argmin over pods).
    Island-batched layout (cfg.island.islands = I > 1): the state's
    leading ISLAND axis is on (pod,), each island's population on
    (model,), and best_* is per island ([I, ...], sharded over pod).
    Returns (step_fn, specs dict) ready for jit/lower;
    step_fn(state, X, y, weight) — weight is the f32[D] dataset-padding
    mask (all-ones when nothing was padded).
    """
    step, state_specs, data_spec, y_spec, w_spec = _pick_step_builder(cfg)(
        cfg, mesh, data_axis=data_axis, model_axis=model_axis, pod_axis=pod_axis)
    smapped = jax.shard_map(
        step, mesh=mesh, check_vma=False,
        in_specs=(state_specs, data_spec, y_spec, w_spec),
        out_specs=state_specs,
    )
    return smapped, dict(state=state_specs, X=data_spec, y=y_spec, weight=w_spec)


def sharded_evolve_block(cfg: GPConfig, mesh, *, n_steps: int, data_axis="data",
                         model_axis="model", pod_axis: str | None = None):
    """Build a shard_map'd K-generation evolution block for `mesh`.

    The `lax.scan` lives INSIDE shard_map, so one dispatch runs `n_steps`
    generations — collectives included — with no host round-trip between
    them. Early stop follows the same branch-free freeze as the
    single-device block; the classic layout's `best_fitness` is
    replicated, the island layout reduces it (min over the pod's local
    islands, `pmin` over the pod axis), so every shard takes the same
    freeze decision either way. Returns (block_fn, specs dict);
    block_fn(state, X, y, weight, limit) -> (state, history, counters) —
    `limit` is the replicated dynamic step budget (pass n_steps to run
    the full block); history is f32[n_steps] replicated for the classic
    layout, f32[n_steps, I] (one per-island best-fitness stream per
    column, sharded over pod) for the island layout; counters is the
    replicated int32[n_steps, C] telemetry stream (repro.obs.counters —
    cache columns are 0 on a mesh).
    """
    island = cfg.island.islands > 1
    n_pods = mesh.shape[pod_axis] if pod_axis else 1
    step, state_specs, data_spec, y_spec, w_spec = _pick_step_builder(cfg)(
        cfg, mesh, data_axis=data_axis, model_axis=model_axis, pod_axis=pod_axis)

    def done(s, i, limit):
        if not (island and cfg.stop_fitness is not None):
            return _block_done(cfg, s, i, limit)
        best = s.best_fitness.min()  # this pod's islands
        if pod_axis:
            best = jax.lax.pmin(best, pod_axis)  # every shard agrees
        d = best <= cfg.stop_fitness
        return d if limit is None else d | (i >= limit)

    def block(state: GPState, X, y, weight, limit):
        def body(s, i):
            d = done(s, i, limit)
            with jax.named_scope("gp.telemetry"):
                row = _counter_row(cfg, s, d, mesh=True, n_pods=n_pods)
            nxt = _freeze(d, s, step(s, X, y, weight))
            return nxt, (nxt.best_fitness, row)

        st, (hist, counters) = jax.lax.scan(body, state, jnp.arange(n_steps))
        return st, hist, counters

    hist_spec = P(None, pod_axis) if island else P()
    smapped = jax.shard_map(
        block, mesh=mesh, check_vma=False,
        in_specs=(state_specs, data_spec, y_spec, w_spec, P()),
        out_specs=(state_specs, hist_spec, P()),
    )
    return smapped, dict(state=state_specs, X=data_spec, y=y_spec, weight=w_spec,
                         limit=P(), history=hist_spec, counters=P())


# --- streaming chunked fitness ------------------------------------------------


def _stream_kernel(cfg: GPConfig):
    kern = fit.get_kernel(cfg.fitness.kernel)
    if kern.moments is None:
        raise ValueError(
            f"fitness kernel {kern.name!r} defines no moment pass "
            f"(moments/reduce_moments), so it cannot accumulate across data "
            f"chunks; register it through the two-pass protocol "
            f"(see docs/fitness-kernels.md) or evaluate monolithic")
    return kern


def chunked_moments(cfg: GPConfig, op, arg, dataset, const_table=None, *,
                    impl: str | None = None):
    """Phase-1 moments of the WHOLE streamed dataset: fold every chunk of
    `dataset` (a `data/loader.ChunkedDataset`, or any iterable of
    fixed-shape `(X_fm, y, weight)` chunks) into an f32[P, M] accumulator
    via the backend's `stream_moments` — one fixed-shape jitted dispatch
    per chunk, so peak device footprint is ONE chunk plus the
    accumulator, independent of total rows. The fold seeds with zeros
    (the kernel-merge identity by contract) and the host drives the chunk
    loop; finalize with `chunked_fitness` or `reduce_moments`."""
    from repro.gp.backends import get_backend

    backend = get_backend(impl or cfg.eval_impl)
    kern = _stream_kernel(cfg)
    if backend.stream_moments is None:
        raise ValueError(f"eval backend {backend.name!r} exposes no "
                         f"stream_moments pass and cannot fold data chunks")
    if const_table is None:
        const_table = cfg.tree_spec.const_table()
    acc = jnp.zeros((op.shape[0], kern.n_moments), jnp.float32)
    for X, y, weight in dataset:
        acc = backend.stream_moments(acc, op, arg, X, y, const_table,
                                     cfg.tree_spec, cfg.fitness, weight=weight,
                                     data_tile=cfg.data_tile)
    return acc


def chunked_fitness(cfg: GPConfig, op, arg, dataset, const_table=None, *,
                    impl: str | None = None):
    """f32[P] fitness of every tree against a chunked data stream:
    `chunked_moments` folded over the chunks, finalized ONCE by the
    kernel's `reduce_moments`. Parity with the monolithic paths is pinned
    by tests/test_stream.py — bitwise for decomposable kernels (their
    merge is an exact weighted sum of per-point terms), ≤1e-4 for the
    centered-moment kernels (pearson/r2), for ANY chunking including a
    ragged zero-weight-padded final chunk."""
    kern = _stream_kernel(cfg)
    m = chunked_moments(cfg, op, arg, dataset, const_table, impl=impl)
    return kern.reduce_moments(jnp.asarray(m), cfg.fitness)


def build_stream_fold(cfg: GPConfig, mesh, *, data_axis: str = "data"):
    """Jitted mesh fold step for streaming chunks, composing chunking
    with the data-axis shard: `fold(acc, op, arg, X, y, weight) -> acc`
    with `acc`/`op`/`arg` replicated and the chunk's `X [F, Dc]` /
    `y [Dc]` / `weight [Dc]` sharded on `data_axis` (Dc % data == 0 —
    `GPSession.ingest` rounds `chunk_rows` up). Each call completes
    phase 1 for its chunk across the mesh (`_merge_moments_on_mesh`:
    psum / hoisted psum / gather+combine, matching the generation step's
    reduction semantics) and merges the replicated result into the
    carried accumulator; finalize the final accumulator once with the
    kernel's `reduce_moments`."""
    kern = _stream_kernel(cfg)
    n_data = mesh.shape[data_axis]

    def fold(acc, op, arg, X, y, weight):
        const_table = cfg.tree_spec.const_table()
        partial_m = _eval_moments(cfg, op, arg, X, y, weight, const_table)
        merged = _merge_moments_on_mesh(kern, cfg.fitness, partial_m, y,
                                        weight, data_axis, n_data)
        return kern.merge_moments(acc, merged, cfg.fitness)

    smapped = jax.shard_map(
        fold, mesh=mesh, check_vma=False,
        in_specs=(P(), P(), P(), P(None, data_axis), P(data_axis),
                  P(data_axis)),
        out_specs=P(),
    )
    return jax.jit(smapped)
