"""The fit driver: repeated GP fits through `GPSession`, as a user who
refits a model runs them. Each fit is `init(key)` then `evolve(n)` with
the generations of the configuration; the window runs fits back to back
and counts the generations of every fit that completed.

Set-up makes the dataset from the seed, builds one session, ingests the
data and runs one whole fit, which compiles (or loads from the cache)
every program the window drives. The window then drives that same
session.

Every fit's published answers are kept on the device during the window
(no copy, no sync) and checked after it closes: the champion and the
elite of the fitness cache, each with the fitness the program published
for it, re-scored by the plain reference over all of the configuration's
rows. The gap is taken to the range of fitness that float32 allows the
tree (`reference.score_range`: division within 3 ulps), where the
kernel has one, and to the IEEE reference's fitness where it has not.

With `control=True` the reference in bfloat16 is put in the program's
place: each published tree's fitness is taken as the bfloat16
reference's score of it, and goes through the same checks, which must
then fail.
"""
from __future__ import annotations

import numpy as np

import reference
from harness import cells, datagen


def _session(cell):
    """A session of the configuration's `gp` keys, each as the program
    names it, and the mix's `session` options."""
    from repro.gp import GPSession

    gp = dict(cell.config["gp"])
    want = gp.pop("require_backend", None)
    sess = GPSession(**gp, **cell.traffic.get("session", {}))
    if want and sess.backend != want:
        raise RuntimeError(f"backend {gp['backend']!r} resolved to "
                           f"{sess.backend!r}, and this cell needs {want!r}")
    return sess


def run(cell, seed: int, seconds: float, window, control: bool = False):
    """Set up, measure for `seconds`, check. Returns the run's record."""
    import jax

    ds = cell.config["dataset"]
    gens = int(cell.config["gp"]["generations"])
    data_seed, key_seed = datagen.seeds(seed, 2)
    X_rows, y = cells.make_dataset(ds, data_seed, cell.root)
    sess = _session(cell)
    sess.ingest(X_rows, y)
    base = jax.random.PRNGKey(key_seed)
    # set-up: one whole fit of the window's shapes, off the window's keys
    sess.init(key=jax.random.fold_in(base, 2**30))
    sess.evolve(gens)
    jax.block_until_ready(sess.state)
    window.setup_done()

    kept = []
    before = dict(sess.stats)
    with window.measure() as clock:
        i = 0
        while True:
            with window.span("fit.init"):
                sess.init(key=jax.random.fold_in(base, i))
            with window.span("fit.evolve"):
                sess.evolve(gens)  # ends in the block-boundary sync
            s = sess.state
            kept.append((s.op, s.best_op, s.best_arg, s.best_fitness,
                         s.cache_op, s.cache_arg, s.cache_fit, s.generation))
            window.tick(gens)
            i += 1
            if clock() >= seconds:
                break
    elapsed = window.elapsed
    window.read_memory()
    counters = {k: sess.stats.get(k, 0) - before.get(k, 0)
                for k in ("tree_evals", "subtree_evals_saved",
                          "unique_subtrees", "migrations", "cache_hits")}
    spec = sess.config.tree_spec
    host = jax.device_get(kept)
    del kept, sess
    checks, active, detail = _check(host, X_rows, y, cell, spec, gens,
                                    control)

    n_fits = len(host)
    pop_total = host[0][0].shape[0]
    rows, feats = X_rows.shape
    traced = window.traced_units
    work = {
        "fits": n_fits, "generations": n_fits * gens,
        "pop_total": pop_total, "rows": rows,
        "mean_active_nodes": active,
        # generations of the fits that ran while the profiler traced
        "traced_generations": traced,
        # their node-row applications: the trees' active nodes (counted
        # on the host from the genomes, as the mean over every fit's
        # last population) times the rows
        "node_row_apps": traced * pop_total * active * rows,
        # one pass over the dataset (X and y, float32) per generation
        "data_bytes": traced * rows * (feats + 1) * 4,
    }
    return {
        "attempted": n_fits, "failed": 0,
        "e2e": {"gens_per_s": n_fits * gens / elapsed},
        "checks": checks, "work": work,
        "counters": counters, "spans": {}, "worst": detail,
    }


def _check(host, X_rows, y, cell, spec, gens, control):
    """Re-score every published champion and elite with the reference."""
    from repro.core.trees import to_string

    gp = cell.config["gp"]
    consts = np.asarray(spec.const_table())
    n_classes = gp["n_classes"]

    def score(text, dtype="float32"):
        return reference.score(text, X_rows, y, gp["kernel"], n_classes,
                               dtype=dtype)

    scored = {}  # expression -> (reference, range): trees recur across fits

    def judge(text):
        if text not in scored:
            scored[text] = (score(text), reference.score_range(
                text, X_rows, y, gp["kernel"], n_classes))
        return scored[text]

    worst, short, actives = 0.0, 0, []
    detail = None  # the tree that reads the widest gap
    for n_fit, (op, bop, barg, bfit, cop, carg, cfit, gen) in enumerate(host):
        short = max(short, gens - int(gen))
        actives.append(float((np.asarray(op) != 0).sum(-1).mean()))
        trees = [(bop, barg, float(bfit))] + [
            (o, a, float(f)) for o, a, f in zip(cop, carg, cfit)]
        for k, (o, a, f) in enumerate(trees):
            text = to_string(o, a, const_table=consts, genome=spec.genome)
            if text == "∅":  # an empty tree: nothing was published
                ref, rng, g = None, None, float("inf")
            else:
                ref, rng = judge(text)
                if control:  # the bfloat16 reference in the program's place
                    f = score(text, "bfloat16")
                g = reference.gap(f, ref, rng)
            if g > worst or detail is None:
                detail = {"fit": n_fit, "tree": ("champion", "elite")[min(k, 1)],
                          "published": f, "reference": ref, "range": rng,
                          "point_gap": None if ref is None
                          else reference.gap(f, ref),
                          "expression": text,
                          "op": o.tolist(), "arg": a.tolist()}
            worst = max(worst, g)
    limits = cell.config["limits"]
    checks = [("fitness_gap", worst, limits["fitness_gap"]),
              ("generations_short", float(short), 0.0)]
    return checks, float(np.mean(actives)), detail
