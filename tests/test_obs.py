"""Observability (repro.obs): the telemetry acceptance gates.

The load-bearing property is FREEDOM FROM OBSERVER EFFECTS — counters
are computed unconditionally inside the compiled evolution blocks, so
turning tracing/metrics on must not recompile anything, add host syncs,
or perturb a single bit of the trajectory. These tests pin that, plus
the trace-file schema (valid Chrome trace JSON, properly nested spans,
paired async job lanes), the program's spans on the profiler's host
plane under the names the Chrome sink writes, the elite-cache hit-rate
surface on both the session and the service, and the `repro.obs.report`
summarizer.
"""
import collections
import contextlib
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine
from repro.data.datasets import kepler
from repro.gp import GPSession
from repro.obs import Metrics, NULL_TRACER, Tracer, counters, validate_trace
from repro.obs import trace as obs_trace
from repro.obs.metrics import BlockMonitor
from repro.service import GPService, JobSpec

PROGRAM_PREFIXES = ("fit.", "serve.")


@contextlib.contextmanager
def _profiler(logdir):
    """A profiler session writing under `logdir` (no Python tracer, as
    in the benchmark's traced window)."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _host_spans(logdir):
    """(name, start_ns, end_ns) of every program span on the host planes
    of the one profile under `logdir`."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.end_ns) for plane in data.planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events if e.name.startswith(PROGRAM_PREFIXES)]


def _chrome_spans(tracer):
    return collections.Counter(e["name"] for e in tracer.events
                               if e["ph"] == "B")


def _inside(child, parents):
    return any(s <= child[1] and child[2] <= e for _, s, e in parents)


def _jobs(n=3, rows=48, seed=0):
    r = np.random.RandomState(seed)
    out = []
    for i in range(n):
        X = r.randn(rows, 3).astype(np.float32)
        y = (X[:, 0] * X[:, 1]).astype(np.float32)
        out.append(JobSpec(X, y, kernel="r", generations=8, seed=i,
                           name=f"obs-{i}"))
    return out


# --- tentpole: no observer effects -------------------------------------------


@pytest.mark.parametrize("profiler", [False, True])
@pytest.mark.parametrize("backend", ["jnp", "pallas"])
@pytest.mark.parametrize("islands", [1, 3])
@pytest.mark.parametrize("genome", ["tree", "postfix"])
def test_telemetry_on_off_bitwise_parity(backend, islands, genome, profiler,
                                         tmp_path):
    """Tracing + metrics ON yields the bitwise-identical best-fitness
    trajectory, the same generation count and the same host-sync budget
    as OFF — across backend × island layout × genome. The counter stream
    is unconditional in the compiled program, so enablement is purely a
    host-side concern. The OFF run's spans are the bare profiler
    annotations, recording nothing; with `profiler` the ON run also
    writes every span to a running profiler session."""
    X_rows, y, _ = kepler()
    kw = dict(pop_size=16, generations=10, kernel="r", backend=backend,
              genome=genome, islands=islands, migrate_every=3, migrate_k=2,
              block_size=5)
    off = GPSession(**kw)
    off.fit(X_rows, y, key=jax.random.PRNGKey(0))

    tracer = Tracer(str(tmp_path / "trace.json"))
    mreg = Metrics(str(tmp_path / "metrics.jsonl"))
    on = GPSession(tracer=tracer, metrics=mreg, **kw)
    with (_profiler(tmp_path / "prof") if profiler
          else contextlib.nullcontext()):
        on.fit(X_rows, y, key=jax.random.PRNGKey(0))
    mreg.close()
    if profiler:
        assert "fit.block" in {n for n, _, _ in _host_spans(tmp_path / "prof")}

    np.testing.assert_array_equal(np.asarray(off.history),
                                  np.asarray(on.history))
    assert on.generation == off.generation
    assert on.stats["host_syncs"] == off.stats["host_syncs"]
    assert on.stats["blocks"] == off.stats["blocks"]
    # telemetry actually flowed on the instrumented run
    assert on.stats["tree_evals"] > 0
    with open(tracer.save()) as f:
        assert validate_trace(json.load(f)) == []


def test_telemetry_does_not_recompile_blocks():
    """Two identically-configured sessions — one silent, one fully
    instrumented — share ONE compiled evolution block: the memoized
    engine cache must not grow when the second (traced) run dispatches."""
    X_rows, y, _ = kepler()
    kw = dict(pop_size=16, generations=8, kernel="r", backend="jnp")
    s0 = GPSession(**kw)
    s0.fit(X_rows, y, key=jax.random.PRNGKey(0))
    n0 = engine.evolve_block._cache_size()
    s1 = GPSession(tracer=Tracer(), metrics=Metrics(), **kw)
    s1.fit(X_rows, y, key=jax.random.PRNGKey(0))
    assert engine.evolve_block._cache_size() == n0
    np.testing.assert_array_equal(np.asarray(s0.history),
                                  np.asarray(s1.history))


def test_counter_stream_accounts_evaluations():
    """The device counter stream's totals land in session stats: a G-
    generation run on pop P evaluates at most G*P trees (less cache
    skips), every step queried the elite cache, and the hit rate is
    consistent with the raw counters."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=16, generations=12, kernel="r", backend="jnp")
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    st = s.stats
    assert st["cache_queries"] == 12
    assert 0 < st["tree_evals"] <= 12 * 16
    assert st["tree_evals"] == 12 * 16 - st["cache_hits"] * 1  # elitism=1
    assert st["cache_hit_rate"] == pytest.approx(
        st["cache_hits"] / st["cache_queries"])


@pytest.mark.parametrize("islands", [1, 3])
def test_node_evals_counts_active_slots(islands):
    """NODE_EVALS of each generation of a block is the host's count of
    the non-EMPTY slots of the rows that generation scored: the PRE-step
    population, less the elite rows the cache served on a hit."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=16, generations=6, kernel="r", backend="jnp",
                  islands=islands, migrate_every=2, migrate_k=2)
    s.ingest(X_rows, y)
    s.init(key=jax.random.PRNGKey(0))
    cfg, K, init = s.config, 6, jax.device_get(s.state)
    want, state = [], init
    for _ in range(K):
        host = jax.device_get(state)
        op, E = np.asarray(host.op), host.cache_op.shape[-2]
        hit = (np.array_equal(op[..., :E, :], host.cache_op)
               and np.array_equal(np.asarray(host.arg)[..., :E, :],
                                  host.cache_arg))
        want.append(int((op != 0).sum() - hit * (op[..., :E, :] != 0).sum()))
        state = engine.evolve_step(cfg, jax.tree.map(jnp.asarray, host),
                                   s._X, s._y, s._weight)
    _, _, rows = engine.evolve_block(cfg, jax.tree.map(jnp.asarray, init),
                                     s._X, s._y, s._weight, n_steps=K)
    rows = np.asarray(rows)
    assert rows[:, counters.NODE_EVALS].tolist() == want
    assert counters.totals(rows)["node_evals"] == sum(want)
    s.adopt_state(init).evolve(K)
    assert s.stats["node_evals"] == sum(want)


def test_node_evals_counts_active_slots_tenant_block():
    """The tenant block's NODE_EVALS is the host's count over the slots
    still evolving: each one's non-EMPTY slots, less its cache-served
    elite rows on a hit. Slot 1's budget runs out after 2 generations,
    so its frozen steps count nothing."""
    from repro.core.trees import TreeSpec

    spec = TreeSpec(max_depth=4, n_features=3, n_consts=8)
    I, P, Dc, K = 3, 16, 64, 5
    state = engine.empty_tenant_state(I, P, spec, elitism=1)
    for i in range(I):
        sub = engine.init_tenant_slot(jax.random.PRNGKey(i), P, spec,
                                      elitism=1)
        state = jax.tree.map(lambda b, s, i=i: b.at[i].set(s), state, sub)
    r = np.random.RandomState(3)
    X = jnp.asarray(r.randn(I, 3, Dc).astype(np.float32))
    y = jnp.asarray(r.randn(I, Dc).astype(np.float32))
    w = jnp.ones((I, Dc), jnp.float32)
    params = engine.TenantParams(
        probs=jnp.tile(jnp.asarray([[0.1, 0.1, 0.1, 0.7]], jnp.float32),
                       (I, 1)),
        tourn=jnp.full((I,), 4, jnp.int32),
        point_rate=jnp.full((I,), 0.1, jnp.float32),
        kernel_id=jnp.zeros((I,), jnp.int32),
        n_classes=jnp.full((I,), 3.0, jnp.float32),
        precision=jnp.full((I,), 1e-4, jnp.float32),
        stop=jnp.full((I,), -jnp.inf, jnp.float32),
        budget=jnp.asarray([K, 2, K], jnp.int32))
    step = jax.jit(engine.build_tenant_block(spec, ("r",), 6, 1, 1))
    want, st = [], state
    for _ in range(K):
        host = jax.device_get(st)
        op, E = np.asarray(host.op), host.cache_op.shape[1]
        active = ((np.asarray(host.gens_done) < np.asarray(params.budget))
                  & ~(np.asarray(host.best_fitness) <= -np.inf))
        n = 0
        for i in np.flatnonzero(active):
            hit = (np.array_equal(op[i, :E], host.cache_op[i])
                   and np.array_equal(np.asarray(host.arg)[i, :E],
                                      host.cache_arg[i]))
            n += int((op[i] != 0).sum() - hit * (op[i, :E] != 0).sum())
        want.append(n)
        st, _, _ = step(st, X, y, w, params)
    _, _, rows = jax.jit(engine.build_tenant_block(spec, ("r",), 6, 1, K))(
        state, X, y, w, params)
    rows = np.asarray(rows)
    assert rows[:, counters.NODE_EVALS].tolist() == want
    assert rows[2:, counters.FROZEN].tolist() == [1] * (K - 2)
    assert want[-1] < want[0]  # the frozen slot's trees are not counted


_SUBPROCESS_MESH_NODE_EVALS = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.data.datasets import iris
from repro.gp import GPSession, MeshTopology
from repro.obs import counters

X_rows, y, _ = iris()
for topo in (MeshTopology(data=2, pod=2), MeshTopology(data=4)):
    s = GPSession(pop_size=16, generations=4, kernel="r", backend="jnp",
                  topology=topo)
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    assert s.stats["tree_evals"] > 0, s.stats
    assert s.stats["node_evals"] == 0, s.stats
solo = GPSession(pop_size=16, generations=4, kernel="r", backend="jnp")
solo.fit(X_rows, y, key=jax.random.PRNGKey(0))
assert solo.stats["node_evals"] > 0, solo.stats
print("MESH_NODE_EVALS_OK")
"""


def test_node_evals_zero_on_a_mesh():
    """On a CPU multi-device mesh NODE_EVALS reads 0, as the cache and
    dedup columns do: a shard holds part of the population, and the
    counter row adds no collective to sum it."""
    import subprocess
    import sys

    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SUBPROCESS_MESH_NODE_EVALS],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MESH_NODE_EVALS_OK" in r.stdout


def test_frozen_steps_counted_not_evaluated():
    """With stop_fitness tripping at generation 1, the rest of the capped
    block self-reports as frozen compute in the counter stream."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=16, generations=40, kernel="r", backend="jnp",
                  stop_fitness=1e9)
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    assert s.generation == 1
    assert s.stats["frozen"] > 0
    assert s.stats["cache_queries"] == 1  # only the live step queried


# --- satellite: elite-cache hit rate on both doors ---------------------------


def test_session_cache_hit_rate_surfaces():
    """A run long enough to converge its elites reports hits > 0; with
    elite_cache=False the counters stay zeroed and the rate is 0."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=16, generations=30, kernel="r", backend="jnp")
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    assert s.stats["cache_hits"] > 0
    assert 0.0 < s.stats["cache_hit_rate"] <= 1.0

    s2 = GPSession(pop_size=16, generations=30, kernel="r", backend="jnp",
                   elite_cache=False)
    s2.fit(X_rows, y, key=jax.random.PRNGKey(0))
    assert s2.stats["cache_hits"] == 0 and s2.stats["cache_queries"] == 0
    assert s2.stats["cache_hit_rate"] == 0.0


def test_host_backend_cache_hit_rate_surfaces():
    """The scalar host loop feeds the same stats surface (satellite: the
    host path is not a telemetry dead zone)."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=12, generations=12, kernel="r", backend="scalar")
    s.fit(X_rows, y)
    assert s.stats["cache_queries"] == 12
    assert s.stats["tree_evals"] > 0
    assert s.stats["blocks"] > 0 and s.stats["block_s_ema"] is not None


def test_service_cache_hit_rate_and_no_recompile(tmp_path):
    """The service aggregates slot-level cache counters; enabling
    tracer + metrics keeps the one-compiled-program guarantee."""
    tracer = Tracer(str(tmp_path / "svc.json"))
    mreg = Metrics(str(tmp_path / "svc.jsonl"))
    svc = GPService(slots=2, pop_size=32, n_features=3, data_cap=64,
                    block_size=4, tracer=tracer, metrics=mreg)
    for j in _jobs(3):
        svc.submit(j)
    svc.run()
    mreg.close()
    assert svc.stats["compiles"] == 1, svc.stats
    assert svc.stats["cache_queries"] > 0
    assert svc.stats["tree_evals"] > 0
    assert 0.0 <= svc.stats["cache_hit_rate"] <= 1.0
    # per-job async lanes all paired, spans all nested
    payload = json.load(open(tracer.save()))
    assert validate_trace(payload) == []
    phases = {e["ph"] for e in payload["traceEvents"]}
    assert {"b", "e", "B", "E"} <= phases
    names = {e["name"] for e in payload["traceEvents"]}
    assert {"serve.admit", "serve.dispatch", "serve.job",
            "serve.publish"} <= names


def test_service_elitism_zero_disables_cache_counters():
    svc = GPService(slots=2, pop_size=32, n_features=3, data_cap=64,
                    block_size=4, elitism=0)
    for j in _jobs(2):
        svc.submit(j)
    svc.run()
    assert svc.stats["cache_hits"] == 0 and svc.stats["cache_queries"] == 0
    assert svc.stats["cache_hit_rate"] == 0.0


# --- satellite: trace schema --------------------------------------------------


def test_trace_schema_and_nesting(tmp_path):
    """A real session run writes valid Chrome trace JSON: envelope,
    nested B/E spans (fit.ingest, fit.block, fit.checkpoint), no orphan
    E events."""
    X_rows, y, _ = kepler()
    path = str(tmp_path / "t.json")
    tracer = Tracer(path)
    s = GPSession(pop_size=16, generations=9, kernel="r", backend="jnp",
                  block_size=3, tracer=tracer,
                  checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=3)
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    tracer.save()
    with open(path) as f:
        payload = json.load(f)
    assert validate_trace(payload) == []
    assert isinstance(payload["traceEvents"], list)
    names = {e["name"] for e in payload["traceEvents"]}
    assert {"fit.ingest", "fit.init_state", "fit.block", "fit.dispatch",
            "fit.sync", "fit.absorb", "fit.checkpoint"} <= names
    # every B has ts/pid/tid — the fields Perfetto needs to lay out lanes
    for ev in payload["traceEvents"]:
        if ev["ph"] in ("B", "E"):
            assert {"ts", "pid", "tid"} <= set(ev)


@pytest.mark.parametrize("path", ["blocks", "stream"])
def test_profiler_sees_session_spans(path, tmp_path):
    """Every session span reaches the profiler's host plane under the
    name the Chrome sink writes, nested as the host loop nests them:
    the population and the restore inside `fit.init_state`, dispatch and
    sync inside `fit.block`, the absorb and the checkpoint after it.
    `fit.init_population` is opened by the engine, which holds no
    tracer, so it is the one span on the profiler alone. No program span
    takes a name of the benchmark's own (`fit.init`, `fit.evolve`)."""
    X_rows, y, _ = kepler()
    tracer = Tracer()
    if path == "blocks":
        kw = dict(pop_size=16, kernel="r", backend="jnp", block_size=3,
                  checkpoint_dir=str(tmp_path / "ck"), checkpoint_every=3)
        GPSession(generations=6, **kw).fit(X_rows, y)  # leaves a checkpoint
        sess = GPSession(generations=6, tracer=tracer, **kw)
        want = {"fit.ingest", "fit.init_state", "fit.init_population",
                "fit.init_restore", "fit.block", "fit.dispatch", "fit.sync",
                "fit.absorb", "fit.checkpoint"}
    else:
        sess = GPSession(pop_size=16, generations=2, kernel="r",
                         backend="jnp", chunk_rows=4, tracer=tracer)
        want = {"fit.ingest", "fit.init_state", "fit.init_population",
                "fit.stream_fold"}
    with _profiler(tmp_path / "prof"):
        sess.fit(X_rows, y, key=jax.random.PRNGKey(0))
    spans = _host_spans(tmp_path / "prof")
    names = collections.Counter(n for n, _, _ in spans)
    assert set(names) == want
    assert names - collections.Counter(["fit.init_population"]) \
        == _chrome_spans(tracer)

    def of(name):
        return [s for s in spans if s[0] == name]

    for child, parent in [("fit.init_population", "fit.init_state"),
                          ("fit.init_restore", "fit.init_state"),
                          ("fit.dispatch", "fit.block"),
                          ("fit.sync", "fit.block")]:
        assert all(_inside(c, of(parent)) for c in of(child)), child
    for after in ("fit.absorb", "fit.checkpoint"):
        assert not any(_inside(c, of("fit.block")) for c in of(after))
    assert not {"fit.init", "fit.evolve"} & set(names)


def test_armed_profile_window_holds_its_block_spans(tmp_path):
    """`profile_dir=`/`profile_block=` profile one block, and that
    window holds the block's own spans, dispatch and sync included."""
    X_rows, y, _ = kepler()
    tracer = Tracer(profile_dir=str(tmp_path / "prof"), profile_block=1)
    GPSession(pop_size=16, generations=9, kernel="r", backend="jnp",
              block_size=3, tracer=tracer).fit(X_rows, y)
    names = collections.Counter(n for n, _, _ in _host_spans(tmp_path / "prof"))
    assert names == {"fit.block": 1, "fit.dispatch": 1, "fit.sync": 1}


def test_profiler_sees_service_spans(tmp_path):
    """The service's admission and dispatch spans reach the profiler as
    they reach the Chrome sink; the job lanes and the publish instant
    are Chrome events only."""
    tracer = Tracer()
    svc = GPService(slots=2, pop_size=32, n_features=3, data_cap=64,
                    block_size=4, tracer=tracer)
    for j in _jobs(3):
        svc.submit(j)
    with _profiler(tmp_path / "prof"):
        svc.run()
    names = collections.Counter(n for n, _, _ in _host_spans(tmp_path / "prof"))
    assert set(names) == {"serve.admit", "serve.dispatch"}
    assert names == _chrome_spans(tracer)


def test_profiler_off_records_nothing(tmp_path):
    """Spans opened while no profiler runs leave nothing behind: a
    profiler session opened afterwards holds none of them."""
    X_rows, y, _ = kepler()
    GPSession(pop_size=16, generations=4, kernel="r", backend="jnp",
              tracer=Tracer()).fit(X_rows, y)
    GPSession(pop_size=16, generations=4, kernel="r",
              backend="jnp").fit(X_rows, y)
    with _profiler(tmp_path / "prof"):
        pass
    assert _host_spans(tmp_path / "prof") == []


def test_span_annotation_carries_the_bare_name(tmp_path):
    """`args` reach the Chrome sink only: on the profiler every door to
    a span — the Tracer, the null tracer and the module helper — writes
    the name alone, so both sinks name a span alike."""
    tracer = Tracer()
    with _profiler(tmp_path / "prof"):
        with tracer.span("fit.a", args={"k": 3}):
            pass
        with NULL_TRACER.span("fit.b", args={"k": 3}):
            pass
        with obs_trace.span("fit.c"):
            pass
    names = [n for n, _, _ in _host_spans(tmp_path / "prof")]
    assert sorted(names) == ["fit.a", "fit.b", "fit.c"]
    assert [e["args"] for e in tracer.events if e["ph"] == "B"] == [{"k": 3}]


def test_validate_trace_catches_malformed():
    assert validate_trace({}) == ["traceEvents is not a list"]
    orphan = {"traceEvents": [
        {"ph": "E", "name": "x", "pid": 1, "tid": 1, "ts": 0.0}]}
    assert any("orphan E" in p for p in validate_trace(orphan))
    unclosed = {"traceEvents": [
        {"ph": "B", "name": "x", "pid": 1, "tid": 1, "ts": 0.0}]}
    assert any("unclosed B" in p for p in validate_trace(unclosed))
    dangling = {"traceEvents": [
        {"ph": "e", "name": "job", "id": "1", "pid": 1, "tid": 1, "ts": 0.0}]}
    assert any("async e without b" in p for p in validate_trace(dangling))


def test_async_lanes_idempotent():
    """Service restart replay can re-open a live lane or re-close a
    closed one; the written trace still pairs b/e exactly once."""
    t = Tracer()
    t.begin_async("job", 7)
    t.begin_async("job", 7)  # replayed admission: no-op
    t.end_async("job", 7)
    t.end_async("job", 7)  # replayed publish: no-op
    payload = {"traceEvents": t.events}
    assert validate_trace(payload) == []
    assert sum(e["ph"] == "b" for e in t.events) == 1
    assert sum(e["ph"] == "e" for e in t.events) == 1


# --- metrics registry ---------------------------------------------------------


def test_metrics_jsonl_and_snapshot(tmp_path):
    path = str(tmp_path / "m.jsonl")
    m = Metrics(path)
    m.inc("widgets", 3)
    m.gauge("depth", 5.0)
    m.observe("lat_s", 0.5)
    m.observe("lat_s", 1.5)
    m.emit("custom", hello=1)
    snap = m.snapshot()
    assert snap["counters"]["widgets"] == 3
    assert snap["gauges"]["depth"] == 5.0
    assert snap["summaries"]["lat_s"]["count"] == 2
    assert snap["summaries"]["lat_s"]["mean"] == pytest.approx(1.0)
    m.close()
    lines = [json.loads(l) for l in open(path)]
    kinds = [l["kind"] for l in lines]
    assert "custom" in kinds and kinds[-1] == "snapshot"


def test_block_monitor_routes_all_timing():
    """Satellite 6: BlockMonitor is THE block-timing path — it updates
    the metrics registry and the legacy stats dict together."""
    from repro.runtime.fault import StepMonitor

    mon = StepMonitor()
    m = Metrics()
    stats = {"blocks": 0, "block_s_ema": None, "stragglers": []}
    bm = BlockMonitor(mon, m, stats)
    for _ in range(3):
        with bm:
            pass
    assert stats["blocks"] == 3
    assert stats["block_s_ema"] == mon.ema
    assert m.counter_value("blocks") == 3
    assert m.summary("block_s")["count"] == 3


def test_counter_helpers():
    rows = np.array([[1, 1, 0, 0, 16, 40, 8, 250],
                     [0, 1, 1, 3, 15, 20, 9, 230]], np.int32)
    tot = counters.totals(rows)
    assert tot == {"cache_hits": 1, "cache_queries": 2, "frozen": 1,
                   "migrations": 3, "tree_evals": 31,
                   "subtree_evals_saved": 60, "unique_subtrees": 17,
                   "node_evals": 480}
    assert counters.hit_rate(tot) == pytest.approx(0.5)
    assert counters.hit_rate({"cache_hits": 0, "cache_queries": 0}) == 0.0


def test_null_tracer_is_inert():
    with NULL_TRACER.span("x"):
        pass
    with NULL_TRACER.maybe_profile(0):
        pass
    NULL_TRACER.instant("x")
    NULL_TRACER.begin_async("x", 1)
    NULL_TRACER.end_async("x", 1)
    assert NULL_TRACER.save() is None


# --- report summarizer --------------------------------------------------------


def test_report_summarizes_run_artifacts(tmp_path, capsys):
    """End to end: run with --trace/--metrics wiring, then the report
    module loads + summarizes both artifacts without error."""
    from repro.obs import report

    X_rows, y, _ = kepler()
    tpath = str(tmp_path / "t.json")
    mpath = str(tmp_path / "m.jsonl")
    tracer, mreg = Tracer(tpath), Metrics(mpath)
    s = GPSession(pop_size=16, generations=10, kernel="r", backend="jnp",
                  tracer=tracer, metrics=mreg)
    s.fit(X_rows, y, key=jax.random.PRNGKey(0))
    tracer.save()
    mreg.close()
    assert report.main([mpath, "--trace", tpath]) == 0
    out = capsys.readouterr().out
    assert "trace: valid" in out
    assert "cache hit rate" in out
    assert "fit.block" in out


def test_absorb_block_telemetry_raw_surface():
    """The raw evolve_block() door keeps its 2-tuple no-sync contract;
    absorb_block_telemetry() is the explicit one-sync hook that folds
    the stashed device counters into stats."""
    X_rows, y, _ = kepler()
    s = GPSession(pop_size=16, generations=20, kernel="r", backend="jnp")
    s.ingest(X_rows, y)
    s.init(key=jax.random.PRNGKey(0))
    syncs0 = s.stats["host_syncs"]
    s.evolve_block(6)
    assert s.stats["host_syncs"] == syncs0  # dispatch alone never syncs
    st = s.absorb_block_telemetry()
    assert s.stats["host_syncs"] == syncs0 + 1
    assert st["cache_queries"] == 6
    assert st["tree_evals"] > 0
