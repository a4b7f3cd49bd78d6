"""GPSession — one front door for every GP run shape.

The paper's claim is one algorithm across platforms and five orders of
magnitude of dataset size; the session makes that a one-line switch:

    from repro.gp import GPSession, MeshTopology

    # single device, auto-selected backend
    GPSession(pop_size=200, kernel="r").fit(X_rows, y)

    # explicit platform (paper's scalar/vector axis)
    GPSession(backend="scalar").fit(X_rows, y)      # 1-CPU_SP baseline
    GPSession(backend="pallas").fit(X_rows, y)      # fused TPU kernel

    # mesh/island run — PartitionSpec plumbing stays internal
    GPSession(topology=MeshTopology(data=2, model=2, pod=2)).fit(X_rows, y)

    # island-model run: 4 islands of 200 trees on ANY of the above —
    # one CPU device, a flat mesh, or pods × in-device islands; the
    # same fit() call, per-island best-fitness streams in
    # session.island_history
    GPSession(pop_size=200, islands=4, migrate_every=5).fit(X_rows, y)

The session owns the full lifecycle: data ingestion (`data/loader`
transposition + padding + device placement), state init/seeding
(`core.parse`), the generation loop, early stopping, periodic
checkpointing (`ckpt/`), and best-tree decoding (`trees.to_string`).

The loop is driven in device-resident *evolution blocks*: `evolve()`
dispatches `engine.evolve_block` (a `lax.scan` over K generations —
`sharded_evolve_block` on a mesh) and synchronizes with the device once
per block, reading back the final state plus the [K] per-generation
best-fitness history. Early stop (`cfg.stop_fitness`) is a branch-free
on-device freeze checked on the host only at block boundaries; the
block size is min(checkpoint period, callback period, remaining
generations), so checkpoints and callbacks still fire exactly when
configured. Datasets whose row count doesn't divide the mesh's data
axis are padded (`data/loader.pad_rows`) with a zero-weight mask that
keeps fitness exact. `session.stats["host_syncs"]` counts the actual
host synchronizations, pinned by tests to ≤ ⌈generations/K⌉.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core import fitness as fit
from repro.core import primitives as prim
from repro.core.engine import GPConfig, GPState
from repro.core.trees import to_string, tree_sizes
from repro.data.loader import feature_major
from repro.gp import backends as _backends
from repro.obs import counters as _tc
from repro.obs.metrics import BlockMonitor, Metrics
from repro.obs.trace import NULL_TRACER
from repro.runtime.fault import StepMonitor as _StepMonitor


@dataclasses.dataclass(frozen=True)
class MeshTopology:
    """Device-mesh shape for a sharded run; `data * model * pod` must not
    exceed the process's device count.

    data   shards dataset columns: `X f32[F, D]`, `y f32[D]` and the
           padding mask `weight f32[D]` split on D; each shard's [P, M]
           fitness moments psum-reduce across this axis (two-pass
           protocol, so every registered kernel — pearson/r2 included —
           shards here). Rows that don't divide `data` are zero-weight
           padded by `GPSession.ingest`, so any row count is legal.
    model  shards the population (op/arg int32[P, N] split on P);
           selection all_gathers the pod's fitness + parent pool (tiny
           next to evaluation).
    pod    island parallelism. Classic layout (islands=1): each pod
           slice evolves an independent sub-population with periodic
           elite ring migration. Island-batched layout (islands=I > 1):
           the pod axis shards the ISLAND axis — I/n_pods in-device
           islands per pod, migration composed across both levels
           (`core/islands.py`); `migrate_every`/`migrate_k`/
           `island_topology` configure it.

    Purely declarative — `build()` materializes the jax Mesh; GPSession
    calls it lazily and keeps all PartitionSpec plumbing internal."""

    data: int = 1
    model: int = 1
    pod: int = 1

    def build(self):
        """Materialize the jax.sharding.Mesh (host-local devices)."""
        from repro.launch.mesh import make_host_mesh

        return make_host_mesh(data=self.data, model=self.model, pod=self.pod)


_TREE_KEYS = ("max_depth", "n_features", "n_consts", "fn_set", "p_const",
              "grow_p_fn", "genome")
_FIT_KEYS = ("kernel", "n_classes", "precision")
# flat spellings of IslandConfig fields (migrate_every/migrate_k ride the
# GPConfig legacy aliases); "islands" is the headline front-door knob
_ISLAND_KEYS = {"islands": "islands", "island_topology": "topology",
                "island_mixes": "mixes", "island_tourn_sizes": "tourn_sizes",
                "island_point_rates": "point_rates"}


def make_config(config: GPConfig | None = None, **overrides) -> GPConfig:
    """GPConfig from flat keyword overrides — tree/fitness/island sub-spec
    keys (max_depth, kernel, islands, island_topology, ...) land on the
    right nested dataclass, so callers never hand-assemble
    TreeSpec/FitnessSpec/IslandConfig for common runs."""
    config = config if config is not None else GPConfig()
    tree_kw = {k: overrides.pop(k) for k in _TREE_KEYS if k in overrides}
    fit_kw = {k: overrides.pop(k) for k in _FIT_KEYS if k in overrides}
    island_kw = {v: overrides.pop(k) for k, v in _ISLAND_KEYS.items()
                 if k in overrides}
    if island_kw:
        config = dataclasses.replace(
            config, island=dataclasses.replace(config.island, **island_kw))
    fn_set = tree_kw.get("fn_set")
    if isinstance(fn_set, str):
        tree_kw["fn_set"] = prim.FunctionSet.make(tuple(fn_set.split(",")))
    elif isinstance(fn_set, (list, tuple)):
        tree_kw["fn_set"] = prim.FunctionSet.make(tuple(fn_set))
    if tree_kw:
        config = dataclasses.replace(
            config, tree_spec=dataclasses.replace(config.tree_spec, **tree_kw))
    if fit_kw:
        config = dataclasses.replace(
            config, fitness=dataclasses.replace(config.fitness, **fit_kw))
    if overrides:
        config = dataclasses.replace(config, **overrides)
    return config


class GPSession:
    """Owns one GP run: config + backend + topology + state + loop.

    Lifecycle: `ingest(X, y)` → `init(key=)` → `evolve(n)` (or `fit`,
    which chains all three). `state` is the device-resident GPState
    pytree (population int32[P, N] op/arg pairs, f32[P] fitness,
    champion tree + f32 best_fitness, int32 generation); properties
    `generation`/`best_fitness` read it back (one host sync each), while
    `history` (floats, one per generation run) and `stats`
    ('host_syncs'/'blocks' counters) are host-side and free to read.
    Keyword overrides (pop_size=, kernel=, max_depth=, ...) land on the
    right nested GPConfig dataclass via `make_config`.

    `islands=I` (plus `migrate_every=`, `migrate_k=`, `island_topology=`,
    `island_mixes=`, `island_tourn_sizes=`, `island_point_rates=`) turns
    the run into I islands of `pop_size` trees on whatever backend and
    topology the session already uses — every GPState population leaf
    grows a leading island axis, `island_history` streams each island's
    best fitness per generation, `best_expression()`/`predict()` decode
    the best across all islands, and `island_expressions()` lists every
    island's champion. With a pod-axis mesh the islands spread over pods
    (islands % pod == 0); `islands=1` is bitwise the classic layout."""

    def __init__(self, config: GPConfig | None = None, *, backend: str | None = None,
                 topology: "MeshTopology | object | None" = None,
                 checkpoint_dir: str | None = None, checkpoint_every: int = 10,
                 feature_names=None, callback=None, callback_every: int = 1,
                 block_size: int | None = None, chunk_rows: int | None = None,
                 tracer=None, metrics=None, **overrides):
        explicit_features = (config is not None or "tree_spec" in overrides
                             or "n_features" in overrides)
        explicit_impl = config is not None or "eval_impl" in overrides
        self._cfg = make_config(config, **overrides)
        if backend is None:
            backend = self._cfg.eval_impl if explicit_impl else "auto"
        self._backend = _backends.get_backend(backend)
        if self._backend.jittable:
            self._cfg = dataclasses.replace(self._cfg, eval_impl=self._backend.name)
        self._explicit_features = explicit_features
        self._topology = topology
        self._mesh = None
        self._step_fn = None  # jitted sharded step (step() contract)
        self._block_cache = {}  # n_steps -> jitted sharded block
        self._built_for = None  # (cfg, mesh) the jitted step was built for
        self._specs = None
        self._X = None
        self._y = None
        self._weight = None  # f32[D'] padding mask (mesh runs only)
        # streaming chunked ingest: evaluate datasets larger than device
        # memory by folding fixed-shape chunks (docs/fitness-kernels.md)
        self._chunk_rows = chunk_rows  # default for ingest(chunk_rows=)
        self._stream = None  # ChunkedDataset when ingest chunked
        self._stream_fold = None  # jitted mesh fold (engine.build_stream_fold)
        self._n_rows = 0  # REAL (pre-padding) row count
        self._gen_host = 0  # host mirror of state.generation (no device read)
        self._gen_dirty = False  # mirror stale (raw evolve_block + stop_fitness)
        self.state: GPState | None = None
        self.history: list[float] = []
        # island runs: one f32[I] row per generation (per-island best-
        # fitness streams); stays empty for the classic layout
        self.island_history: list[np.ndarray] = []
        self.stats = {"host_syncs": 0, "blocks": 0, "block_s_ema": None,
                      "stragglers": [], "cache_hits": 0, "cache_queries": 0,
                      "cache_hit_rate": 0.0, "frozen": 0, "migrations": 0,
                      "tree_evals": 0, "node_evals": 0}
        self._monitor = _StepMonitor()  # per-block wall time EMA + stragglers
        # observability (repro.obs): tracer spans + metrics registry are
        # host-side only — the compiled programs are identical with or
        # without them (the counter stream is unconditional), so these
        # defaults cost nothing and enabling them changes no trajectory
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics if metrics is not None else Metrics()
        # THE timing path for every block dispatch — jitted, host-loop,
        # and streamed alike — so block_s_ema/stragglers report everywhere
        self._block_monitor = BlockMonitor(self._monitor, self.metrics,
                                           self.stats)
        self._last_counters = None  # device [K, C] from a raw evolve_block
        self.feature_names = list(feature_names) if feature_names else None
        self._callback = callback
        self._callback_every = max(1, int(callback_every))
        self._block_size = block_size
        self._manager = None
        if checkpoint_dir:
            from repro.ckpt.checkpoint import CheckpointManager

            self._manager = CheckpointManager(checkpoint_dir, every=checkpoint_every)
        if topology is not None and not self._backend.supports_topology:
            raise ValueError(f"backend {self._backend.name!r} does not support "
                             f"mesh topologies (host-only)")

    # --- introspection -------------------------------------------------------

    @property
    def config(self) -> GPConfig:
        return self._cfg

    @property
    def backend(self) -> str:
        return self._backend.name

    @property
    def generation(self) -> int:
        return int(self.state.generation) if self.state is not None else 0

    @property
    def islands(self) -> int:
        """Number of islands in the population layout (1 = classic)."""
        return self._cfg.island.islands

    @property
    def best_fitness(self) -> float:
        """Best fitness seen so far — across ALL islands for an
        island-batched run (one host sync)."""
        if self.state is None:
            return float("inf")
        bf = np.asarray(self.state.best_fitness)
        return float(bf.min()) if bf.ndim else float(bf)

    @property
    def island_best_fitness(self) -> np.ndarray:
        """f32[I] per-island champion fitness (one host sync)."""
        self._require_state()
        return np.atleast_1d(np.asarray(self.state.best_fitness))

    @property
    def n_rows(self) -> int:
        """REAL data points currently ingested (0 before ingest; excludes
        any zero-weight padding added to shard exactly)."""
        return self._n_rows

    @property
    def mesh(self):
        if self._mesh is None and self._topology is not None:
            top = self._topology
            self._mesh = top.build() if isinstance(top, MeshTopology) else top
        return self._mesh

    def _pod_axis(self):
        mesh = self.mesh
        return "pod" if mesh is not None and "pod" in mesh.axis_names else None

    def build_sharded_step(self):
        """(step_fn, specs) of the mesh generation step — step_fn(state,
        X, y, weight); `step()` drives it internally."""
        if self.mesh is None:
            raise ValueError("build_sharded_step needs a topology= mesh")
        return engine.sharded_evolve_step(self._cfg, self.mesh,
                                          pod_axis=self._pod_axis())

    def build_sharded_block(self, n_steps: int):
        """(block_fn, specs) of the K-generation mesh evolution block —
        the lowering surface used by launch/dryrun.py; `evolve()` drives
        it internally. block_fn(state, X, y, weight, limit) ->
        (state, history, counters)."""
        if self.mesh is None:
            raise ValueError("build_sharded_block needs a topology= mesh")
        return engine.sharded_evolve_block(self._cfg, self.mesh, n_steps=n_steps,
                                           pod_axis=self._pod_axis())

    # --- lifecycle -----------------------------------------------------------

    def ingest(self, X=None, y=None, *, layout: str = "rows",
               sample_weight=None, stream=None,
               chunk_rows: int | None = None) -> "GPSession":
        """Load the dataset onto the session's devices. layout='rows' is
        sklearn-style [rows, features] float data (transposed to the
        paper's feature-major f32[F, D] Eq. 2 form internally);
        layout='features' accepts already-transposed [features, rows].
        y is f32[D] targets (class ids as floats for the 'c' kernel).
        `sample_weight` (f32[D], optional) scales each point's fitness
        contribution; 0.0 excludes a point exactly (every kernel's
        padding contract), so pre-padded data — e.g. a service job's
        slot buffer replayed solo — evaluates bit-for-bit. On a mesh,
        rows that don't divide the data axis are padded with a
        zero-weight mask (fitness stays exact; `n_rows` reports the real
        count; sample weights compose with the mask) and X/y/weight are
        device_put sharded; single-device jittable backends get plain
        device arrays; host-only backends keep numpy. Synchronous host
        work only — no device compute.

        Streaming front door — datasets larger than device memory:
        `chunk_rows=` (here or on the constructor) evaluates X/y as a
        fold over fixed `[F, chunk_rows]` zero-weight-padded chunks, and
        `stream=` accepts a `data/loader.ChunkedDataset`, a memmapped
        array, or a callable/iterator of `(X, y[, weight])` row blocks.
        Fitness parity with monolithic ingest is pinned (bitwise for
        decomposable kernels, ≤1e-4 for pearson/r2); evolution advances
        one generation per host-driven chunk fold, so peak device
        footprint is ONE chunk regardless of total rows. On a mesh each
        chunk is sharded on the data axis (chunk_rows rounds up to a
        multiple of it)."""
        with self.tracer.span("fit.ingest"):
            out = self._ingest(X, y, layout=layout,
                               sample_weight=sample_weight, stream=stream,
                               chunk_rows=chunk_rows)
        self.metrics.gauge("rows", self._n_rows)
        return out

    def _ingest(self, X=None, y=None, *, layout, sample_weight, stream,
                chunk_rows) -> "GPSession":
        if stream is not None or chunk_rows is not None or (
                self._chunk_rows is not None):
            return self._ingest_stream(X, y, layout=layout,
                                       sample_weight=sample_weight,
                                       stream=stream, chunk_rows=chunk_rows)
        self._stream = None
        self._stream_fold = None
        if X is None or y is None:
            raise ValueError("ingest needs X and y (or stream=)")
        X = np.asarray(X, np.float32)
        y = np.asarray(y, np.float32)
        if sample_weight is not None:
            sample_weight = np.asarray(sample_weight, np.float32)
        if layout == "rows":
            X_fm = feature_major(X)
        elif layout == "features":
            X_fm = np.ascontiguousarray(X)
        else:
            raise ValueError(f"layout must be 'rows' or 'features', got {layout!r}")
        F, D = X_fm.shape
        if y.shape != (D,):
            raise ValueError(f"y shape {y.shape} does not match {D} data points")
        spec = self._cfg.tree_spec
        if spec.n_features != F:
            if self._explicit_features:
                raise ValueError(f"TreeSpec.n_features={spec.n_features} but the "
                                 f"dataset has {F} features")
            self._cfg = dataclasses.replace(
                self._cfg, tree_spec=dataclasses.replace(spec, n_features=F))

        self._n_rows = D
        if sample_weight is not None and sample_weight.shape != (D,):
            raise ValueError(f"sample_weight shape {sample_weight.shape} does "
                             f"not match {D} data points")
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            from repro.data.loader import pad_feature_major

            # pad rows up to the data axis; the zero-weight mask threads
            # through every fitness kernel, so sharding is always exact
            n_data = self.mesh.shape["data"]
            X_fm, y, w = pad_feature_major(X_fm, y, n_data)
            if sample_weight is not None:
                w = w * np.pad(sample_weight, (0, w.shape[0] - D))
            if self._step_fn is None or self._built_for != (self._cfg, self.mesh):
                # warm_start refits reuse the jitted programs; rebuild only
                # when the config or mesh actually changed
                step, self._specs = self.build_sharded_step()
                with jax.set_mesh(self.mesh):
                    self._step_fn = jax.jit(step, donate_argnums=(0,))
                self._block_cache = {}
                self._built_for = (self._cfg, self.mesh)
            self._X = jax.device_put(X_fm, NamedSharding(self.mesh, P(None, "data")))
            self._y = jax.device_put(y, NamedSharding(self.mesh, P("data")))
            self._weight = jax.device_put(w, NamedSharding(self.mesh, P("data")))
        elif self._backend.jittable:
            self._X = jnp.asarray(X_fm)
            self._y = jnp.asarray(y)
            # single device never pads; an explicit weight threads through
            self._weight = None if sample_weight is None else jnp.asarray(sample_weight)
        else:
            self._X, self._y = X_fm, y
            self._weight = sample_weight
        self._invalidate_elite_cache()
        return self

    def _invalidate_elite_cache(self):
        """New data invalidates the elite fitness cache (cached scores
        were measured against the old dataset) — reset to the
        never-matching init, so the next generation re-evaluates."""
        if self.state is not None and self.state.cache_fit.size:
            self.state = self.state._replace(
                cache_op=jnp.zeros_like(self.state.cache_op),
                cache_arg=jnp.zeros_like(self.state.cache_arg),
                cache_fit=jnp.full_like(self.state.cache_fit, jnp.inf))

    def _ingest_stream(self, X, y, *, layout, sample_weight, stream,
                       chunk_rows) -> "GPSession":
        """Streaming half of `ingest`: wrap the source in a fixed-shape
        `ChunkedDataset` (or adopt one), infer n_features from it, and
        arm the per-generation chunk fold. On a mesh, `chunk_rows` rounds
        up to a multiple of the data axis and `engine.build_stream_fold`
        shards every chunk exactly like the monolithic step would."""
        from repro.data.loader import ChunkedDataset

        if stream is not None and X is not None:
            raise ValueError("pass either X/y or stream=, not both")
        chunk_rows = chunk_rows if chunk_rows is not None else self._chunk_rows
        n_data = self.mesh.shape["data"] if self.mesh is not None else 1
        if isinstance(stream, ChunkedDataset):
            ds = stream
            if chunk_rows is not None and int(chunk_rows) != ds.chunk_rows:
                raise ValueError(f"chunk_rows={chunk_rows} conflicts with the "
                                 f"ChunkedDataset's chunk_rows={ds.chunk_rows}")
            if ds.chunk_rows % n_data:
                raise ValueError(f"chunk_rows={ds.chunk_rows} must be a "
                                 f"multiple of the mesh data axis ({n_data})")
        else:
            if chunk_rows is None:
                raise ValueError("stream= needs chunk_rows= (constructor or "
                                 "ingest keyword), or pass a ChunkedDataset")
            rows = int(chunk_rows)
            rows += (-rows) % n_data  # mesh: every chunk shards exactly
            ds = ChunkedDataset(stream if stream is not None else X, y,
                                chunk_rows=rows, layout=layout,
                                sample_weight=sample_weight)
        F = ds.n_features
        spec = self._cfg.tree_spec
        if spec.n_features != F:
            if self._explicit_features:
                raise ValueError(f"TreeSpec.n_features={spec.n_features} but "
                                 f"the dataset has {F} features")
            self._cfg = dataclasses.replace(
                self._cfg, tree_spec=dataclasses.replace(spec, n_features=F))
        self._stream = ds
        self._X = self._y = self._weight = None
        self._n_rows = ds.n_rows or 0
        self._stream_fold = (engine.build_stream_fold(self._cfg, self.mesh)
                             if self.mesh is not None else None)
        self._invalidate_elite_cache()
        return self

    def init(self, *, key=None, seeds=None) -> "GPSession":
        """Fresh state (or checkpoint restore when a checkpoint_dir holds
        one). `seeds` are expression strings — Karoo's customized seed
        populations, parsed against the session's TreeSpec."""
        if self._X is None and self._stream is None:
            raise ValueError("no dataset — call ingest()/fit() first")
        key = key if key is not None else jax.random.PRNGKey(0)
        with self.tracer.span("fit.init_state"):
            self.state = engine.init_state(self._cfg, key, seeds=seeds,
                                           feature_names=self.feature_names)
            self.history = []
            self.island_history = []
            self._gen_host = 0
            self._gen_dirty = False
            if self._manager is not None:
                with self.tracer.span("fit.init_restore"):
                    restored, step = self._manager.restore_latest(
                        like=jax.device_get(self.state))
                    if restored is not None:
                        self.state = jax.tree.map(jnp.asarray, restored)
                        self._gen_host = int(step)
        return self

    # --- slot-level state swap (the service scheduler's surface) -------------

    def export_island(self, idx: int):
        """Island `idx`'s slice of the session state as an un-batched
        sub-state pytree (leading island axis dropped; the shared
        generation scalar rides along unchanged) — what a multi-tenant
        scheduler lifts out of a batch when a slot's job finishes. Pure
        host-eager slicing; no recompilation, no state mutation."""
        from repro.core.islands import take_island

        self._require_state()
        if self.islands <= 1:
            raise ValueError("export_island needs an island-batched run "
                             "(islands > 1)")
        if not 0 <= idx < self.islands:
            raise ValueError(f"island {idx} out of range [0, {self.islands})")
        return take_island(self.state, idx)

    def import_island(self, idx: int, sub) -> "GPSession":
        """Replace island slot `idx` with `sub` (an `export_island` slice
        or any identically-shaped sub-state, e.g. a freshly initialized
        one) — admission half of the slot swap. Eager `.at[].set`
        updates on the live state; the compiled step/block programs are
        untouched, so swapping populations between blocks never triggers
        a recompile."""
        from repro.core.islands import splice_island

        self._require_state()
        if self.islands <= 1:
            raise ValueError("import_island needs an island-batched run "
                             "(islands > 1)")
        if not 0 <= idx < self.islands:
            raise ValueError(f"island {idx} out of range [0, {self.islands})")
        self.state = splice_island(self.state, idx, sub)
        return self

    def adopt_state(self, state: GPState) -> "GPSession":
        """Install an externally built GPState (a checkpoint restored and
        resharded elsewhere, a spliced batch, ...) as the session's live
        state and resynchronize the host generation mirror — one host
        sync, then the evolve loop continues from it as if the session
        had produced it."""
        self.state = jax.tree.map(jnp.asarray, state)
        self._gen_host = int(self.state.generation)
        self._gen_dirty = False
        return self

    def step(self) -> GPState:
        """One generation, unconditionally (no early-stop freeze). Does not
        synchronize with the device — callers timing the hot loop
        (benchmarks/) see pure step throughput."""
        if self.state is None:
            self.init()
        if self._stream is not None:
            # streamed datasets fold chunk-by-chunk on the host loop —
            # every backend and layout, mesh included (the fold shards
            # each chunk on the data axis)
            self.state = self._host_step(self.state)
        elif self._step_fn is not None:
            with jax.set_mesh(self.mesh):
                self.state = self._step_fn(self.state, self._X, self._y,
                                           self._weight)
        elif self._backend.jittable:
            self.state = engine.evolve_step(self._cfg, self.state, self._X,
                                            self._y, self._weight)
        else:
            self.state = self._host_step(self.state)
        self._gen_host += 1
        return self.state

    def evolve_block(self, n_steps: int) -> tuple[GPState, jax.Array]:
        """Run `n_steps` generations in ONE device dispatch (`lax.scan`
        block; scan-inside-shard_map on a mesh). Updates the session state
        and returns (state, history) WITHOUT synchronizing with the host —
        history is the device-resident f32[n_steps] best-fitness stream.
        The block's telemetry counter stream stays device-resident too;
        `absorb_block_telemetry()` folds it into `stats` on demand (one
        sync), while `evolve()` — which drives this and owns the
        block-boundary bookkeeping — absorbs it for free as part of each
        block's single boundary sync."""
        state, history, _ = self._dispatch_block(n_steps, n_steps)
        if self._cfg.stop_fitness is None:
            self._gen_host += n_steps  # exact: no freeze possible
        else:
            self._gen_dirty = True  # frozen steps may not have advanced it
        return state, history

    def _dispatch_block(self, n_steps: int, limit: int):
        """One block dispatch: a compiled program of `n_steps` scan steps,
        of which only the first `limit` advance (the rest freeze) — so one
        program serves every ragged boundary ≤ n_steps. No host sync, no
        generation bookkeeping. Returns (state, history, counters) with
        counters the device-resident int32[n_steps, C] telemetry stream
        (repro.obs.counters)."""
        if self.state is None:
            self.init()
        if self._stream is not None:
            raise ValueError("streamed/chunked datasets advance one generation "
                             "per host-driven chunk fold; evolution blocks "
                             "need a device-resident dataset (drive the run "
                             "with evolve() instead)")
        if not self._backend.jittable:
            raise ValueError(f"backend {self._backend.name!r} is host-only; "
                             f"evolution blocks need a jittable backend")
        if self.mesh is not None:
            block_fn = self._block_cache.get(n_steps)
            if block_fn is None:
                block, _ = self.build_sharded_block(n_steps)
                with jax.set_mesh(self.mesh):
                    block_fn = jax.jit(block, donate_argnums=(0,))
                self._block_cache[n_steps] = block_fn
            with jax.set_mesh(self.mesh):
                self.state, history, counters = block_fn(
                    self.state, self._X, self._y, self._weight,
                    jnp.asarray(limit, jnp.int32))
        else:
            self.state, history, counters = engine.evolve_block(
                self._cfg, self.state, self._X, self._y, self._weight,
                jnp.asarray(limit, jnp.int32), n_steps=n_steps)
        self._last_counters = counters
        return self.state, history, counters

    # --- telemetry accounting (repro.obs) ------------------------------------

    def _count_host_sync(self, n: int = 1):
        """THE host-sync accounting point. Every path that synchronizes
        with the device counts through here (the counter once drifted
        across three independent increment sites), and the obs metrics
        registry sees the same number the `stats` pin tests do."""
        self.stats["host_syncs"] += n
        self.metrics.inc("host_syncs", n)

    def _absorb_counters(self, rows):
        """Fold an int32[K, C] telemetry block (repro.obs.counters) into
        `stats` and the metrics registry: cache hits/queries (and the
        derived `cache_hit_rate`), frozen steps, migrations, and tree
        evaluations (× the real row count for trees·rows)."""
        tot = _tc.totals(rows)
        for name, v in tot.items():
            self.stats[name] = self.stats.get(name, 0) + v
            if v:
                self.metrics.inc(name, v)
        self.stats["cache_hit_rate"] = _tc.hit_rate(self.stats)
        self.metrics.gauge("cache_hit_rate", self.stats["cache_hit_rate"])
        if self._n_rows and tot["tree_evals"]:
            # int64 host math — the device stream stays int32-safe
            self.metrics.inc("tree_row_evals", tot["tree_evals"] * self._n_rows)
        self.metrics.emit("counters", **tot)

    def _record_host_eval(self, hit: int, queries: int, evals: int,
                          nodes: int):
        """Host-path twin of the device counter stream: the scalar/stream
        generation loops compute their elite-cache gate on the host, so
        the same telemetry columns land without any device work."""
        if queries:
            self.stats["cache_queries"] += queries
            self.metrics.inc("cache_queries", queries)
        if hit:
            self.stats["cache_hits"] += hit
            self.metrics.inc("cache_hits", hit)
        self.stats["tree_evals"] += evals
        self.metrics.inc("tree_evals", evals)
        self.stats["node_evals"] += nodes
        self.metrics.inc("node_evals", nodes)
        self.stats["cache_hit_rate"] = _tc.hit_rate(self.stats)

    def absorb_block_telemetry(self) -> dict:
        """Fold the latest raw `evolve_block()` dispatch's counter stream
        into `stats` (ONE host sync) and return `stats`. `evolve()` does
        this automatically inside each block's boundary sync; this hook
        is for raw-block drivers (benchmarks) that want the cache hit
        rate afterwards."""
        if self._last_counters is not None:
            rows = jax.device_get(self._last_counters)
            self._last_counters = None
            self._count_host_sync()
            self._absorb_counters(rows)
        return self.stats

    def _eval_rows(self, op, arg):
        """Host-side fitness of genome rows [R, N] -> np.f32[R] against the
        session dataset — monolithic (one backend call) or streamed (a
        chunk fold over `self._stream`, finalized once). The streaming
        path composes with a mesh: each chunk is placed with the data-axis
        sharding and folded through the shard_map'd program from
        engine.build_stream_fold, so the reduction semantics match the
        device generation step exactly."""
        cfg = self._cfg
        if self._stream is None:
            return np.asarray(self._backend.fitness(
                np.asarray(op), np.asarray(arg),
                self._X, self._y, np.asarray(cfg.tree_spec.const_table()),
                cfg.tree_spec, cfg.fitness, weight=self._weight,
                data_tile=cfg.data_tile), np.float32)
        kern = fit.get_kernel(cfg.fitness.kernel)
        op, arg = jnp.asarray(op), jnp.asarray(arg)
        if self._stream_fold is not None:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as P

            sh_X = NamedSharding(self.mesh, P(None, "data"))
            sh_y = NamedSharding(self.mesh, P("data"))
            acc = jnp.zeros((op.shape[0], kern.n_moments), jnp.float32)
            with jax.set_mesh(self.mesh):
                for X, y, w in self._stream:
                    # per-chunk host-side cost (place + dispatch; the fold
                    # itself is async) — no sync is added for timing
                    t0 = time.perf_counter()
                    with self.tracer.span("fit.chunk"):
                        acc = self._stream_fold(acc, op, arg,
                                                jax.device_put(X, sh_X),
                                                jax.device_put(y, sh_y),
                                                jax.device_put(w, sh_y))
                    self.metrics.observe("chunk_s", time.perf_counter() - t0)
            fitness = kern.reduce_moments(acc, cfg.fitness)
        else:
            t0 = time.perf_counter()
            with self.tracer.span("fit.stream_fold"):
                fitness = engine.chunked_fitness(cfg, op, arg, self._stream,
                                                 impl=self._backend.name)
            self.metrics.observe("stream_fold_s", time.perf_counter() - t0)
        if self._stream.n_rows is not None:
            self._n_rows = self._stream.n_rows
        return np.asarray(fitness, np.float32)

    def _host_step(self, state: GPState) -> GPState:
        """Generation loop body for non-jittable (host) backends — same
        contract as engine.evolve_step, with evaluation on the host. The
        selection/variation program is jitted ONCE per (spec, mix,
        tourn_size, elitism) and cached across call sites and sessions
        (backends.host_next_generation). Island-batched state loops the
        host evaluator over islands, breeds each with its own operator
        parameters, and applies the in-device migration lowering — the
        scalar baseline runs the same island semantics as the jitted
        paths (per-generation host sync, as ever)."""
        cfg = self._cfg
        if cfg.island.islands > 1:
            return self._host_step_islands(state)
        eval_rows = self._eval_rows

        # host mirror of engine._cached_fitness: exact genome match on the
        # elite head skips its re-evaluation (bitwise-identical — cached
        # fitness IS last generation's evaluation of the same rows)
        E = state.cache_op.shape[0]
        op_h, arg_h = np.asarray(state.op), np.asarray(state.arg)
        hit = E and (np.array_equal(op_h[:E], np.asarray(state.cache_op))
                     and np.array_equal(arg_h[:E], np.asarray(state.cache_arg)))
        self._record_host_eval(int(bool(hit)), 1 if E else 0,
                               op_h.shape[0] - (E if hit else 0),
                               int((op_h[E if hit else 0:] != prim.EMPTY).sum()))
        if hit:
            fitness = np.concatenate([np.asarray(state.cache_fit),
                                      eval_rows(op_h[E:], arg_h[E:])])
        else:
            fitness = eval_rows(op_h, arg_h)
        i = int(fitness.argmin())
        improved = fitness[i] < float(state.best_fitness)
        best_op = state.op[i] if improved else state.best_op
        best_arg = state.arg[i] if improved else state.best_arg
        best_fit = min(float(fitness[i]), float(state.best_fitness))
        sel = fitness
        if cfg.parsimony:
            sel = fitness + cfg.parsimony * np.asarray(tree_sizes(state.op), np.float32)
        if E:
            # jnp.argsort (stable) — same tie-break order as the jitted
            # next_generation's elite pick, so the cached rows are exactly
            # the elites it will place at [:E] next generation
            best = np.asarray(jnp.argsort(jnp.asarray(sel)))[:E]
            cache = (jnp.asarray(op_h[best]), jnp.asarray(arg_h[best]),
                     jnp.asarray(fitness[best]))
        else:
            cache = (state.cache_op, state.cache_arg, state.cache_fit)
        key, k_next = jax.random.split(state.key)
        next_gen = _backends.host_next_generation(
            cfg.tree_spec, cfg.mix, cfg.tourn_size, cfg.elitism)
        new_op, new_arg = next_gen(k_next, state.op, state.arg, jnp.asarray(sel))
        return GPState(key, new_op, new_arg, jnp.asarray(fitness), best_op, best_arg,
                       jnp.asarray(best_fit, jnp.float32), state.generation + 1,
                       *cache)

    def _host_step_islands(self, state: GPState) -> GPState:
        """Island generation on a host-only backend: evaluate the
        flattened [I·P] population in one backend call, breed per island
        through the cached vmapped selection program, migrate across the
        island axis (islands.migrate_local)."""
        from repro.core import islands as isl

        cfg = self._cfg
        icfg = cfg.island
        I, P, N = state.op.shape
        op2 = np.asarray(state.op).reshape(I * P, N)
        arg2 = np.asarray(state.arg).reshape(I * P, N)
        eval_rows = self._eval_rows

        # one ALL-islands hit gate, mirroring engine._island_step_body
        E = state.cache_op.shape[1]
        op3, arg3 = op2.reshape(I, P, N), arg2.reshape(I, P, N)
        hit = E and (np.array_equal(op3[:, :E], np.asarray(state.cache_op))
                     and np.array_equal(arg3[:, :E], np.asarray(state.cache_arg)))
        self._record_host_eval(int(bool(hit)), 1 if E else 0,
                               I * P - (I * E if hit else 0),
                               int((op3[:, E if hit else 0:] != prim.EMPTY).sum()))
        if hit:
            tail = eval_rows(op3[:, E:].reshape(-1, N),
                             arg3[:, E:].reshape(-1, N)).reshape(I, P - E)
            fitness = np.concatenate([np.asarray(state.cache_fit), tail], axis=1)
        else:
            fitness = eval_rows(op2, arg2).reshape(I, P)
        i_best = fitness.argmin(axis=1)
        rows = np.arange(I)
        cand_fit = fitness[rows, i_best]
        improved = cand_fit < np.asarray(state.best_fitness)
        best_op = jnp.where(improved[:, None], np.asarray(state.op)[rows, i_best],
                            state.best_op)
        best_arg = jnp.where(improved[:, None], np.asarray(state.arg)[rows, i_best],
                             state.best_arg)
        best_fit = jnp.minimum(jnp.asarray(cand_fit), state.best_fitness)
        sel = fitness
        if cfg.parsimony:
            sizes = np.asarray(tree_sizes(jnp.asarray(op2)), np.float32)
            sel = fitness + cfg.parsimony * sizes.reshape(I, P)
        if E:
            best = np.asarray(jnp.argsort(jnp.asarray(sel), axis=-1))[:, :E]
            rows_e = np.arange(I)[:, None]
            cache = (jnp.asarray(op3[rows_e, best]),
                     jnp.asarray(arg3[rows_e, best]),
                     jnp.asarray(fitness[rows_e, best]))
        else:
            cache = (state.cache_op, state.cache_arg, state.cache_fit)
        next_gen = _backends.host_next_generation_islands(
            cfg.tree_spec, icfg, cfg.mix, cfg.tourn_size, cfg.elitism)
        keys, new_op, new_arg = next_gen(state.key, state.op, state.arg,
                                         jnp.asarray(sel))
        if icfg.migrate_k and I > 1:
            e_op, e_arg = isl.island_elites(state.op, state.arg,
                                            jnp.asarray(fitness), icfg.migrate_k)
            new_op, new_arg = isl.migrate_local(
                icfg, new_op, new_arg, e_op, e_arg, state.generation,
                jnp.asarray(cand_fit))
        return GPState(keys, new_op, new_arg, jnp.asarray(fitness), best_op,
                       best_arg, best_fit, state.generation + 1, *cache)

    def _block_span(self, remaining: int) -> int:
        """Block size K = min(checkpoint period, callback period, explicit
        block_size, remaining) — every host-visible side effect lands on a
        block boundary, so larger periods buy longer device residency.
        Periods are PHASE-ALIGNED to the absolute generation counter (the
        next boundary lands ON the period's multiple), so `maybe_save`'s
        `step % every == 0` test and the callback cadence hold no matter
        how earlier blocks, resumes, or early stops offset the counter."""
        k = remaining
        if self._manager is not None:
            every = self._manager.every
            k = min(k, every - self._gen_host % every)
        if self._callback is not None:
            k = min(k, self._callback_every - self._gen_host % self._callback_every)
        if self._block_size is not None:
            k = min(k, self._block_size)
        return max(1, k)

    # frozen steps are branch-free selects, NOT skips — they still run the
    # full evaluation. With stop_fitness armed but no period configured,
    # cap the block so a converged run overshoots at most this many
    # generations of device compute before the host notices.
    _STOP_CHECK_SPAN = 32

    def _block_quantum(self, total: int) -> int:
        """Compiled block-program length: the smallest configured period
        (every `_block_span` is ≤ it), so ONE compiled scan serves every
        boundary — ragged phase-alignment gaps and the final partial block
        run with a dynamic `limit` instead of a fresh compile."""
        periods = [p for p in (
            self._manager.every if self._manager is not None else None,
            self._callback_every if self._callback is not None else None,
            self._block_size) if p is not None]
        if periods:
            return max(1, min(periods))
        if self._cfg.stop_fitness is not None:
            return max(1, min(total, self._STOP_CHECK_SPAN))
        return max(1, total)

    def _resync_gen(self):
        """Re-read the generation counter from the device — needed only
        after raw `evolve_block()` calls under stop_fitness, where frozen
        steps may not have advanced it. One host sync."""
        if self._gen_dirty:
            self._gen_host = int(self.state.generation)
            self._count_host_sync()
            self._gen_dirty = False

    def _evolve_host(self, total: int) -> GPState:
        """Per-generation host loop for non-jittable backends (each
        generation already synchronizes — blocks would buy nothing)."""
        cfg = self._cfg
        for i in range(total):
            # the block monitor wraps EVERY loop path (a host generation
            # is a one-step block), so block_s_ema/stragglers report here
            # too, not just on the jitted block loop
            with self._block_monitor:
                self.step()
            bf = np.asarray(self.state.best_fitness)
            if bf.ndim:  # island run: keep the per-island streams too
                self.island_history.append(bf.copy())
            best = float(bf.min()) if bf.ndim else float(bf)
            self.history.append(best)
            self._count_host_sync()
            if self._manager is not None:
                with self.tracer.span("fit.checkpoint"):
                    self._manager.maybe_save(self.state, self._gen_host)
            stopped = cfg.stop_fitness is not None and best <= cfg.stop_fitness
            if self._callback is not None and (
                    self._gen_host % self._callback_every == 0
                    or stopped or i == total - 1):
                self._callback(self._gen_host - 1, self.state)
            if stopped:
                break
        return self.state

    def evolve(self, generations: int | None = None) -> GPState:
        """Drive `generations` generations (default: config.generations) in
        device-resident blocks: one dispatch AND one host synchronization
        per block. Checkpointing, the callback, history extension and the
        stop_fitness check all happen at block boundaries; within a block,
        early stop is the engine's branch-free on-device freeze — no extra
        host round-trips, and the device-compute overshoot is bounded by
        the block span (_STOP_CHECK_SPAN when only stop_fitness is set)."""
        if self.state is None:
            self.init()
        cfg = self._cfg
        total = generations if generations is not None else cfg.generations
        if not self._backend.jittable or self._stream is not None:
            self._evolve_host(total)
        else:
            self._resync_gen()
            target = self._gen_host + total
            quantum = self._block_quantum(total)
            while self._gen_host < target:
                # K never exceeds the compiled block length: with
                # stop_fitness armed but no period, span = remaining >
                # quantum, and an uncapped K would misread the full
                # block (ran == quantum < K) as an early-stop freeze
                # and silently truncate the run
                K = min(self._block_span(target - self._gen_host), quantum)
                prev_gen = self._gen_host
                block_idx = self.stats["blocks"]
                # the monitor times dispatch THROUGH the block-boundary
                # sync — the span a straggling host/device would stretch
                # (the armed profiler window opens first, so that it
                # holds this block's own span)
                with self._block_monitor, \
                        self.tracer.maybe_profile(block_idx), \
                        self.tracer.span("fit.block",
                                         args={"k": K, "quantum": quantum}):
                    with self.tracer.span("fit.dispatch"):
                        _, history, counters = self._dispatch_block(quantum, K)
                    # ONE sync per block: final generation counter, the
                    # best-fitness stream and the telemetry counter
                    # stream come back together
                    with self.tracer.span("fit.sync"):
                        gen_now, hist, crows = jax.device_get(
                            (self.state.generation, history, counters))
                with self.tracer.span("fit.absorb"):
                    gen_now = int(gen_now)
                    self._count_host_sync()
                    self._last_counters = None  # absorbed here, same sync
                    self._absorb_counters(crows)
                    ran = gen_now - prev_gen
                    self._gen_host = gen_now
                    self.metrics.gauge("generation", gen_now)
                    if ran and self._monitor.last:
                        self.metrics.gauge("gens_per_s",
                                           ran / self._monitor.last)
                    rows = hist[:ran]
                    if hist.ndim == 2:  # island run: [K, I] per island
                        self.island_history.extend(np.asarray(rows))
                        rows = rows.min(axis=1)
                    self.history.extend(float(b) for b in rows)
                    stopped = ran < K or (
                        cfg.stop_fitness is not None and ran
                        and rows[ran - 1] <= cfg.stop_fitness)
                    last = stopped or gen_now >= target
                if self._manager is not None:
                    with self.tracer.span("fit.checkpoint"):
                        self._manager.maybe_save(self.state, gen_now)
                if self._callback is not None and ran and (
                        gen_now % self._callback_every == 0 or last):
                    self._callback(gen_now - 1, self.state)
                if stopped:
                    break
        if self._manager is not None:
            # final save, unless the last block boundary already saved here
            with self.tracer.span("fit.checkpoint"):
                self._manager.wait()
                if (not self._manager.saved_steps
                        or self._manager.saved_steps[-1] != self._gen_host):
                    self._manager.maybe_save(self.state, self._gen_host,
                                             force=True)
                self._manager.wait()
        return self.state

    def fit(self, X, y, *, layout: str = "rows", generations: int | None = None,
            key=None, seeds=None, warm_start: bool = False) -> "GPSession":
        """ingest + init + evolve. With warm_start=True an existing evolved
        state continues on the new data instead of reinitializing."""
        self.ingest(X, y, layout=layout)
        if self.state is None or not warm_start:
            self.init(key=key, seeds=seeds)
        self.evolve(generations)
        return self

    # --- results -------------------------------------------------------------

    def _champion(self) -> tuple[np.ndarray, np.ndarray]:
        """(best_op, best_arg) of the overall champion as host arrays —
        for island runs, the best tree across ALL islands (one sync)."""
        self._require_state()
        best_op, best_arg, bf = jax.device_get(
            (self.state.best_op, self.state.best_arg, self.state.best_fitness))
        if np.ndim(bf):
            i = int(np.argmin(bf))
            best_op, best_arg = best_op[i], best_arg[i]
        return np.asarray(best_op), np.asarray(best_arg)

    def best_expression(self) -> str:
        """The champion tree decoded to an infix string (feature names
        substituted when the session has them) — the best across all
        islands for an island-batched run. Reads best_op/best_arg back
        from the device — one host sync."""
        op, arg = self._champion()
        return to_string(op, arg, feature_names=self.feature_names,
                         const_table=np.asarray(self._cfg.tree_spec.const_table()),
                         genome=self._cfg.tree_spec.genome)

    def island_expressions(self) -> list[str]:
        """Each island's champion decoded to an infix string (a length-1
        list for the classic layout) — one host sync."""
        self._require_state()
        best_op, best_arg = jax.device_get((self.state.best_op,
                                            self.state.best_arg))
        best_op, best_arg = np.atleast_2d(best_op), np.atleast_2d(best_arg)
        consts = np.asarray(self._cfg.tree_spec.const_table())
        return [to_string(o, a, feature_names=self.feature_names,
                          const_table=consts,
                          genome=self._cfg.tree_spec.genome)
                for o, a in zip(best_op, best_arg)]

    def predict(self, X, *, layout: str = "rows") -> np.ndarray:
        """Best tree evaluated on new data via this session's backend:
        X [rows, features] (or [features, rows] with layout='features')
        -> f32[rows] predictions, copied back to the host (one sync).
        Single-device only — prediction is one tree, never worth a mesh."""
        self._require_state()
        X = np.asarray(X, np.float32)
        X_fm = feature_major(X) if layout == "rows" else X
        best_op, best_arg = self._champion()
        preds = self._backend.evaluate(
            jnp.asarray(best_op)[None], jnp.asarray(best_arg)[None],
            jnp.asarray(X_fm), self._cfg.tree_spec.const_table(), self._cfg.tree_spec)
        return np.asarray(preds)[0]

    def score(self, X, y, *, layout: str = "rows") -> float:
        """The fitness kernel's human-facing metric (FitnessKernel.metric)
        of the best tree on (X, y) — fraction correct for classify/match,
        mean |err| for regression, R² for r2 — as a host float (syncs)."""
        preds = self.predict(X, layout=layout)
        metric = fit.get_kernel(self._cfg.fitness.kernel).metric(
            jnp.asarray(preds)[None], jnp.asarray(y, jnp.float32), self._cfg.fitness)
        return float(np.asarray(metric)[0])

    def _require_state(self):
        if self.state is None:
            raise ValueError("session has no evolved state — call fit() first")

    # --- dataset convenience -------------------------------------------------

    @classmethod
    def from_dataset(cls, dataset: str, *, max_rows: int | None = None,
                     config: GPConfig | None = None, **kw) -> "GPSession":
        """Session pre-loaded with one of the paper's datasets (data/
        datasets.py), kernel and function set defaulted from its metadata."""
        from repro.data.datasets import BY_NAME

        X_rows, y, meta = BY_NAME[dataset]()
        if max_rows is not None and X_rows.shape[0] > max_rows:
            X_rows, y = X_rows[:max_rows], y[:max_rows]
        if config is None:
            kw.setdefault("name", f"karoo-{dataset}")
            kw.setdefault("kernel", meta["kernel"])
            if "n_classes" in meta:
                kw.setdefault("n_classes", meta["n_classes"])
            kw.setdefault("fn_set", prim.KITCHEN_SINK if meta["kernel"] == "r"
                          else prim.CLASSIFY_SET)
            kw.setdefault("feature_names", meta.get("features"))
        sess = cls(config, **kw)
        return sess.ingest(X_rows, y)
