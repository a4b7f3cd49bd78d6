"""The on-device telemetry counter stream — column contract.

Every evolution-block scan body (`engine.evolve_block`,
`engine.sharded_evolve_block`, `engine.build_tenant_block`) emits one
`int32[C]` counter row per scanned generation alongside the
best-fitness stream, so a block dispatch returns an `int32[K, C]`
telemetry block that rides back to the host with the SAME single
block-boundary sync as the state and history — telemetry never adds a
host round-trip, and because the counters are computed unconditionally
the compiled program is identical whether a Tracer/Metrics sink is
attached or not (tracing on/off is purely a host-side decision, pinned
bitwise by tests/test_obs.py).

Columns (index into the trailing axis; see docs/observability.md):

    CACHE_HITS     elite-cache hit gates that matched this generation
                   (0/1 single-population and island layouts — one
                   all-islands gate; per-slot for the tenant batch)
    CACHE_QUERIES  hit gates evaluated (0 when the cache is disabled,
                   so hits/queries is the run's cache hit rate)
    FROZEN         scan steps (slots, for the tenant batch) that ran
                   frozen this generation — early-stopped, past the
                   dynamic block `limit`, or an empty/finished tenant
                   slot; their compute was executed and discarded
    MIGRATIONS     island-migration events that came due
    TREE_EVALS     productive tree evaluations: population rows scored
                   against the full dataset, excluding cache-served
                   rows and frozen steps (multiply by the real row
                   count for the paper's trees·rows metric)
    SUBTREE_EVALS_SAVED
                   subtree evaluations the exact-tier dedup avoided
                   this generation: total active subtree spans across
                   the PRE-step population minus the distinct count
                   (0 when dedup is off, the genome is not postfix, or
                   the plan overflowed its cap and fell back)
    UNIQUE_SUBTREES
                   distinct subexpressions in the PRE-step population
                   (0 when dedup is off or the genome is not postfix;
                   still the true distinct count when the plan
                   overflowed, which is how a too-small cap shows up
                   in telemetry) — saved / (saved + unique) is the
                   generation's duplicate rate
    NODE_EVALS     active (non-EMPTY) genome slots of the rows TREE_EVALS
                   counts, cache-served rows excluded, terminals
                   included: an upper bound on the tree kernel's
                   per-slot work (only a function slot does vector
                   work; a terminal is a bank row index)

Mesh notes: the sharded step bodies carry the elite cache through
untouched (it is host/single-device machinery), so CACHE_* columns are
0 on a mesh; the dedup columns are likewise 0 on a mesh and in the
tenant batch (re-running the signature sort per shard/slot purely for
telemetry would double the plan cost); NODE_EVALS is 0 on a mesh too
(where a shard holds part of the population, summing it would add a
collective to every generation); every other column is computed from
replicated quantities and is identical on all shards.
"""
from __future__ import annotations

COUNTERS = ("cache_hits", "cache_queries", "frozen", "migrations",
            "tree_evals", "subtree_evals_saved", "unique_subtrees",
            "node_evals")
(CACHE_HITS, CACHE_QUERIES, FROZEN, MIGRATIONS, TREE_EVALS,
 SUBTREE_EVALS_SAVED, UNIQUE_SUBTREES, NODE_EVALS) = range(8)
N_COUNTERS = len(COUNTERS)


def totals(rows) -> dict:
    """Sum an `int32[K, C]` telemetry block into a {column: int} dict —
    the host-side absorption step (`GPSession`/`GPService` fold these
    into their `stats`)."""
    import numpy as np

    rows = np.asarray(rows)
    if rows.ndim == 1:
        rows = rows[None]
    tot = rows.sum(axis=0)
    return {name: int(tot[i]) for i, name in enumerate(COUNTERS)}


def hit_rate(stats: dict) -> float:
    """cache_hits / cache_queries from a stats dict (0.0 before any
    query — a disabled cache never divides by zero)."""
    q = stats.get("cache_queries", 0)
    return stats.get("cache_hits", 0) / q if q else 0.0
