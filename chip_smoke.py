#!/usr/bin/env python3
"""Chip smoke test: drive the GP system's front doors once on a TPU and
check that the answers are right.

    python chip_smoke.py                # one chip: phases A, B and C
    python chip_smoke.py --four-chips   # four chips: mesh and island phases

One chip (the default):

  A  `GPSession` fit, tree genome, `backend="auto"` (which must resolve
     to the Pallas kernels): KAT-7 at the paper's 90,000 rows x 9
     features, classification kernel `c`, population 256, depth 5,
     seed 0, 10 generations in one evolution block. The final
     population's fitness from the Pallas backend must equal the `jnp`
     backend's bit for bit, and the champion re-scored on the host by
     the scalar interpreter (`core/scalar_eval`) must match.
  B  The same fit with `genome="postfix"` and the default
     `dedup="exact"`: the postfix kernel and the dedup kernels, checked
     like A, plus fitness with dedup on equal to dedup off bit for bit.
  C  `GPService` drains 8 heterogeneous jobs of the kind
     `launch/serve_gp.synthetic_stream` makes; every job must reach DONE
     and every published champion must re-score on the host to its
     reported fitness.

Four chips (`--four-chips`, and nothing else):

  D  the phase-A fit on `MeshTopology(data=4)` against its one-device run
  E  4 islands in a ring over `MeshTopology(pod=4)` (ppermute migration)
     against the same 4 islands on one device

  From the same initial population, one generation on four devices must
  score every tree as the one-device run does (the counts of kernel `c`
  leave no room for rounding), with state and data on four devices.

The script runs in one process and falls back to nothing: it exits 1,
and prints no result, when JAX finds no TPU or when the repository's
`src/` is not next to it. Every line but the last is information
(compile and warm seconds, generations/s, each check). The last line is
one JSON object: {"ok": true, "device": {"platform", "kind", "count"}}.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
import traceback
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ROWS, POP, DEPTH, GENS, SEED = 90_000, 256, 5, 10, 0
N_JOBS = 8
# the repo's scalar-vs-vector parity tolerance (tests/test_postfix.py);
# on kernel c's hit counts below 2**24 it admits no difference at all
RTOL, ATOL = 1e-5, 1e-4


class CheckFailed(AssertionError):
    pass


def log(msg: str):
    print(msg, flush=True)


def check(ok: bool, what: str):
    log(f"  [{'pass' if ok else 'FAIL'}] {what}")
    if not ok:
        raise CheckFailed(what)


def _host_device():
    """Context placing jnp work on the host CPU, so the reference
    re-score shares nothing with the chip (a no-op if JAX was started
    without its CPU backend)."""
    import jax

    try:
        return jax.default_device(jax.devices("cpu")[0])
    except RuntimeError:
        return contextlib.nullcontext()


def _rescore(op, arg, X_rows, y, spec, fs):
    """Host fitness of one genome row over every data row: the paper's
    per-point scalar interpreter, the plain reference."""
    import numpy as np

    from repro.core.scalar_eval import fitness_scalar

    with _host_device():
        return float(fitness_scalar(
            np.asarray(op)[None], np.asarray(arg)[None], X_rows, y,
            np.asarray(spec.const_table()), kernel=fs.kernel,
            n_classes=fs.n_classes, precision=fs.precision,
            genome=spec.genome)[0])


def _session(genome: str, **kw):
    from repro.core import primitives as prim
    from repro.gp import GPSession

    return GPSession(pop_size=POP, max_depth=DEPTH, kernel="c", n_classes=2,
                     fn_set=prim.CLASSIFY_SET, genome=genome,
                     generations=GENS, backend="auto", **kw)


def _timed_fit(sess, X_rows, y, label: str):
    """Cold fit (compile included), then the same fit again warm."""
    import jax

    t0 = time.perf_counter()
    sess.fit(X_rows, y, key=jax.random.PRNGKey(SEED))
    jax.block_until_ready(sess.state)
    cold = time.perf_counter() - t0
    first = list(sess.history)
    t0 = time.perf_counter()
    sess.init(key=jax.random.PRNGKey(SEED))
    sess.evolve(GENS)
    jax.block_until_ready(sess.state)
    warm = time.perf_counter() - t0
    log(f"  {label}: cold fit {cold:.3f} s (compile included), warm fit "
        f"{warm:.3f} s = {GENS / warm:.2f} generations/s "
        f"(informational, one run)")
    check(sess.history[-GENS:] == first,
          f"{label}: the warm refit repeats the cold fit's history")
    return sess


def phase_fit(genome: str):
    """Phases A (tree) and B (postfix + dedup)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import engine
    from repro.core.eval import dedup_stats
    from repro.data import datasets
    from repro.data.loader import feature_major
    from repro.gp import get_backend
    from repro.kernels import ops as kops

    X_rows, y, meta = datasets.kat7(rows=ROWS)
    assert meta["kernel"] == "c" and meta["n_classes"] == 2
    sess = _session(genome)
    check(sess.backend == "pallas",
          f"backend='auto' resolved to {sess.backend!r} (want 'pallas')")
    _timed_fit(sess, X_rows, y, f"{genome} fit, P={POP} D={ROWS}")
    check(sess.stats["blocks"] == 2,
          f"each fit ran its {GENS} generations as one evolution block")
    cfg = sess.config
    spec, fs = cfg.tree_spec, cfg.fitness
    if genome == "postfix":
        check(cfg.dedup == "exact", f"dedup={cfg.dedup!r} (the default)")

    X = jnp.asarray(feature_major(X_rows))
    yd = jnp.asarray(y)
    hlo = engine.evolve_block.lower(
        cfg, sess.state, X, yd, None, jnp.asarray(GENS, jnp.int32),
        n_steps=GENS).compile().as_text()
    n_calls = hlo.count('custom_call_target="tpu_custom_call"')
    check(n_calls > 0, f"compiled evolution block holds {n_calls} "
                       f"tpu_custom_call (Pallas kernels)")

    op, arg = sess.state.op, sess.state.arg
    ct = spec.const_table()
    pallas, ref = get_backend("pallas"), get_backend("jnp")
    f_pal = np.asarray(pallas.fitness(op, arg, X, yd, ct, spec, fs))
    f_jnp = np.asarray(ref.fitness(op, arg, X, yd, ct, spec, fs))
    check(np.array_equal(f_pal, f_jnp),
          f"final population (P={POP}): pallas fitness == jnp fitness, "
          f"bitwise (best {f_pal.min():.0f})")
    if genome == "postfix":
        # a unique table of exactly n_unique + 1 rows never overflows, so
        # the dedup kernel — not the plain fallback — produces the fitness;
        # the whole population's table spills the gather to HBM, a
        # 16-tree slice's fits VMEM beside the tile
        K, S = spec.n_features + spec.n_consts, spec.stack_size
        for rows in (POP, 16):
            o, a = op[:rows], arg[:rows]
            n_unique = int(dedup_stats(o, a, spec, rows * spec.num_nodes + 1)[0])
            cap = n_unique + 1
            f_off = np.asarray(pallas.fitness(o, a, X, yd, ct, spec, fs))
            f_dd = np.asarray(pallas.fitness(o, a, X, yd, ct, spec, fs,
                                             dedup="exact", dedup_cap=cap))
            _, db = kops.pick_tiles_postfix(K, S, rows, ROWS)
            gather = ("in-VMEM gather" if kops._postfix_vmem(K, S, 8, db, cap)
                      <= kops._VMEM_BUDGET else "HBM spill")
            check(np.array_equal(f_dd, f_off),
                  f"{rows} trees: dedup on == off, bitwise ({n_unique} unique "
                  f"subtrees, {gather} kernel)")

    best_op, best_arg, best = jax.device_get(
        (sess.state.best_op, sess.state.best_arg, sess.state.best_fitness))
    t0 = time.perf_counter()
    host = _rescore(best_op, best_arg, X_rows, y, spec, fs)
    check(bool(np.isclose(host, float(best), rtol=RTOL, atol=ATOL)),
          f"champion re-scored on the host by scalar_eval over {ROWS} rows: "
          f"{host:.0f} vs {float(best):.0f} "
          f"({time.perf_counter() - t0:.1f} s)")


def phase_service():
    """Phase C: the job service drains a heterogeneous stream."""
    import numpy as np

    from repro.core.fitness import FitnessSpec
    from repro.launch.serve_gp import serve, synthetic_stream
    from repro.service import DONE

    jobs = synthetic_stream(N_JOBS, seed=SEED)
    t0 = time.perf_counter()
    svc, handles = serve(jobs, log=lambda *a: None)
    log(f"  service: {N_JOBS} jobs in {svc.stats['blocks']} blocks, "
        f"{time.perf_counter() - t0:.3f} s wall (compile included, "
        f"informational), {svc.stats['compiles']} compiled program")
    check(all(h.status == DONE for h in handles),
          f"all {N_JOBS} jobs DONE: {[h.status for h in handles]}")
    for h in handles:
        s = h.spec
        fs = FitnessSpec(s.kernel, n_classes=s.n_classes,
                         precision=s.precision)
        host = _rescore(h.best_op, h.best_arg, s.X, s.y, svc.tree_spec, fs)
        check(bool(np.isclose(host, h.best_fitness, rtol=RTOL, atol=ATOL)),
              f"{s.name} ({s.kernel}, {s.n_rows} rows, {h.gens_done} gens): "
              f"host re-score {host:.6g} vs published {h.best_fitness:.6g}")


def _on_four_devices(arr, what: str, split_axis: int | None = None):
    """Check that `arr` lives on four devices (split along `split_axis`
    when given, else replicated)."""
    devices = {s.device for s in arr.addressable_shards}
    shapes = {s.data.shape for s in arr.addressable_shards}
    if split_axis is None:
        ok = len(devices) == 4 and shapes == {arr.shape}
    else:
        want = list(arr.shape)
        want[split_axis] //= 4
        ok = len(devices) == 4 and shapes == {tuple(want)}
    check(ok, f"{what} {arr.shape} on {len(devices)} devices, shards "
              f"{sorted(shapes)}")


def _compare_first_generation(label, multi, single, X_rows, y):
    """One generation from the same initial population on both layouts:
    every tree's fitness must agree — what the tier-2 CPU mesh tests pin
    (`test_blocks`, `test_islands`). Whole trajectories are not compared:
    the sharded steps derive their breeding keys per shard by design."""
    import jax
    import numpy as np

    for sess in (multi, single):
        if sess.n_rows == 0:
            sess.ingest(X_rows, y)
        sess.init(key=jax.random.PRNGKey(SEED))
        sess.step()
    a, b = np.asarray(multi.state.fitness), np.asarray(single.state.fitness)
    check(a.shape == b.shape and bool(np.allclose(a, b, rtol=RTOL, atol=ATOL)),
          f"{label}: first-generation fitness of all {a.size} trees matches "
          f"the one-device run of seed {SEED} "
          f"(bitwise: {bool(np.array_equal(a, b))})")
    check(multi.best_fitness == single.best_fitness,
          f"{label}: first-generation champion fitness "
          f"{multi.best_fitness:.0f} == {single.best_fitness:.0f}")


def phase_mesh():
    """Phase D: the data=4 mesh fit against one device."""
    from repro.data import datasets
    from repro.gp import MeshTopology

    X_rows, y, _ = datasets.kat7(rows=ROWS)
    multi = _timed_fit(_session("tree", topology=MeshTopology(data=4)),
                       X_rows, y, "data=4 mesh fit")
    check(multi.backend == "pallas", f"mesh backend {multi.backend!r}")
    _on_four_devices(multi.state.op, "population (replicated over data)")
    _on_four_devices(multi._X, "dataset X (sharded over data)", split_axis=1)
    _compare_first_generation("data=4", multi, _session("tree"), X_rows, y)


def phase_islands():
    """Phase E: 4 islands over pod=4 with ring migration against the same
    islands on one device."""
    from repro.data import datasets
    from repro.gp import MeshTopology

    X_rows, y, _ = datasets.kat7(rows=ROWS)
    kw = dict(islands=4, island_topology="ring", migrate_every=3,
              migrate_k=4)
    multi = _timed_fit(_session("tree", topology=MeshTopology(pod=4), **kw),
                       X_rows, y, "4 islands over pod=4")
    _on_four_devices(multi.state.op, "island populations (split over pod)",
                     split_axis=0)
    check(multi.stats["migrations"] > 0,
          f"{multi.stats['migrations']} island migrations ran across chips")
    _compare_first_generation("pod=4 islands", multi, _session("tree", **kw),
                              X_rows, y)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh and island phases")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repository source at {SRC}", file=sys.stderr)
        return 1
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (device 0 is {dev.platform!r}); "
              f"this test runs on the chip only", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 1

    from repro.runtime.compile_cache import CacheProbe, enable_compile_cache

    cache = enable_compile_cache()
    probe = CacheProbe()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    phases = ([("D data=4 mesh fit", phase_mesh),
               ("E pod=4 island ring", phase_islands)] if args.four_chips
              else [("A tree fit", lambda: phase_fit("tree")),
                    ("B postfix fit, dedup exact", lambda: phase_fit("postfix")),
                    ("C service drain", phase_service)])
    failed = []
    for name, run in phases:
        log(f"phase {name}")
        t0 = time.perf_counter()
        try:
            run()
        except Exception:  # report every phase, then fail the run
            traceback.print_exc()
            failed.append(name)
        log(f"phase {name}: {'FAILED' if name in failed else 'passed'} "
            f"({time.perf_counter() - t0:.1f} s)")
    log(f"compile cache: {probe.hits} hits, {probe.misses} misses "
        f"({'warm' if probe.hits else 'cold'})")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
