"""Island-model evolution: first-class population layout on any topology.

The paper never leaves a single system board (§3.4: "Karoo was not tested
across a tightly coupled parallel cluster"). The classic GP island model
is how real deployments use many cores/devices: independent
sub-populations with decorrelated RNG, cross-pollinated by periodic
elite migration. Here islands are a *population layout*, not a device
requirement: an evolution run is `I` islands of `P` trees
(`op: int32[I, P, N]`) that

  * runs entirely on ONE device (the island axis is vmapped through the
    generation step, migration is a `jnp.roll`/gather over the leading
    axis),
  * or shards the island axis over the mesh `pod` axis (migration
    lowers to `lax.ppermute`, the multi-device story),
  * or BOTH at once — pods × in-device islands, where the two lowerings
    compose: in-device routing moves elites between a pod's local
    islands and the pod-boundary islands exchange via `ppermute`.

`IslandConfig` also carries the *heterogeneous search* knobs: per-island
operator mixes, tournament sizes and point-mutation rates become arrays
vmapped through `evolve.next_generation_arrays`, so one compiled program
runs I different search regimes and migration cross-pollinates them.

Migration volume is O(I · k · nodes) bytes — negligible against
evaluation — and overlaps with the generation step under XLA's scheduler.

Topologies (`IslandConfig.topology`):

  ring            island i's elites replace the last-k offspring slots
                  of island (i+1) mod I (global ring over pods × local
                  islands, pod-major order)
  torus           islands arranged on a 2D grid (pods × local islands on
                  a mesh, else the squarest factorization of I);
                  migration events alternate east / south shifts
  broadcast-best  the island holding the generation's best tree sends
                  its elites to every island
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.evolve import OperatorMix

TOPOLOGIES = ("ring", "torus", "broadcast-best")


@dataclasses.dataclass(frozen=True)
class IslandConfig:
    """Island layout + migration policy + per-island search knobs.

    islands        number of islands I (1 = the classic single-population
                   layout; state keeps its legacy un-batched shapes)
    migrate_every  generations between migration events
    migrate_k      elites exchanged per event (replace the receiving
                   island's last k offspring slots)
    topology       "ring" | "torus" | "broadcast-best" (see module doc)
    mixes          optional per-island OperatorMix tuple (len == islands)
                   — heterogeneous operator regimes; None = GPConfig.mix
                   everywhere
    tourn_sizes    optional per-island tournament sizes (len == islands);
                   None = GPConfig.tourn_size everywhere
    point_rates    optional per-island point-mutation redraw
                   probabilities (len == islands); None = the 0.25
                   default everywhere
    """

    islands: int = 1
    migrate_every: int = 10
    migrate_k: int = 4
    topology: str = "ring"
    mixes: tuple = None
    tourn_sizes: tuple = None
    point_rates: tuple = None

    def __post_init__(self):
        if self.topology not in TOPOLOGIES:
            raise ValueError(f"unknown island topology {self.topology!r}; "
                             f"one of {TOPOLOGIES}")
        if self.islands < 1:
            raise ValueError(f"islands must be >= 1, got {self.islands}")
        if self.migrate_every < 1:
            # generation % 0 inside jit is silent platform-defined garbage
            raise ValueError(f"migrate_every must be >= 1, got "
                             f"{self.migrate_every}")
        if self.migrate_k < 0:
            raise ValueError(f"migrate_k must be >= 0, got {self.migrate_k}")
        for name in ("mixes", "tourn_sizes", "point_rates"):
            val = getattr(self, name)
            if val is not None:
                object.__setattr__(self, name, tuple(val))
                if len(getattr(self, name)) != self.islands:
                    raise ValueError(f"IslandConfig.{name} has "
                                     f"{len(getattr(self, name))} entries for "
                                     f"{self.islands} islands")

    def __hash__(self):
        return hash((self.islands, self.migrate_every, self.migrate_k,
                     self.topology, self.mixes, self.tourn_sizes,
                     self.point_rates))

    # --- heterogeneous-search parameter arrays (host-side, static) ----------

    def prob_table(self, default_mix: OperatorMix) -> np.ndarray:
        """f32[I, 4] operator-mix probabilities per island."""
        mixes = self.mixes or (default_mix,) * self.islands
        return np.stack([m.probs() for m in mixes])

    def tourn_table(self, default_size: int) -> tuple[int, np.ndarray]:
        """(static max draw size, int32[I] per-island active sizes)."""
        sizes = self.tourn_sizes or (default_size,) * self.islands
        return int(max(sizes)), np.asarray(sizes, np.int32)

    def point_rate_table(self) -> np.ndarray:
        """f32[I] per-island point-mutation redraw probabilities."""
        rates = self.point_rates or (0.25,) * self.islands
        return np.asarray(rates, np.float32)


def torus_grid(islands: int) -> tuple[int, int]:
    """The squarest (rows, cols) factorization of `islands` — the island
    grid the single-device torus topology routes on. Prime counts
    degenerate to (1, I): a ring."""
    r = 1
    for d in range(int(np.sqrt(islands)), 0, -1):
        if islands % d == 0:
            r = d
            break
    return r, islands // r


def take_island(state, idx):
    """Island `idx`'s slice of an island-batched state pytree: leaves with
    a leading island axis lose it ([I, ...] -> [...]), scalar leaves (the
    shared generation counter) pass through unchanged. The inverse of
    `splice_island` — together they are the slot-level state swap the
    multi-tenant service uses to move one job's evolution state in and
    out of a batch."""
    return jax.tree.map(lambda a: a[idx] if jnp.ndim(a) else a, state)


def splice_island(state, idx, sub):
    """Replace island slot `idx` of an island-batched state pytree with
    `sub` (one island's un-batched leaves, as produced by `take_island`
    or a fresh per-job init). Leaves whose rank matches the batched
    leaf's (shared scalars) keep the batched value. Host-eager `.at[]`
    updates — call between block dispatches, not inside jit."""
    def put(a, v):
        if jnp.ndim(a) == jnp.ndim(v):
            return a  # shared leaf (e.g. the lockstep generation scalar)
        return a.at[idx].set(v)

    return jax.tree.map(put, state, sub)


def island_elites(op, arg, fitness, k: int):
    """Per-island top-k trees of the just-evaluated population.

    op/arg: int32[I, P, N], fitness: f32[I, P] → int32[I, k, N] pairs,
    best-first."""
    order = jnp.argsort(fitness, axis=-1)[:, :k]  # [I, k]
    return (jnp.take_along_axis(op, order[:, :, None], axis=1),
            jnp.take_along_axis(arg, order[:, :, None], axis=1))


def _route_local(icfg: IslandConfig, elite_op, elite_arg, event_idx, fit_best):
    """In-device routing: [I, k, N] elites → the [I, k, N] arrivals each
    island receives, per `icfg.topology`. `event_idx` (traced int32) is
    the migration-event counter (torus alternates direction on its
    parity); `fit_best` (f32[I]) picks broadcast-best's champion."""
    I = elite_op.shape[0]
    if icfg.topology == "ring":
        return jnp.roll(elite_op, 1, axis=0), jnp.roll(elite_arg, 1, axis=0)
    if icfg.topology == "torus":
        r, c = torus_grid(I)

        def shift(x):
            g = x.reshape(r, c, *x.shape[1:])
            east = jnp.roll(g, 1, axis=1).reshape(x.shape)
            south = jnp.roll(g, 1, axis=0).reshape(x.shape)
            return jnp.where(event_idx % 2 == 0, east, south)

        return shift(elite_op), shift(elite_arg)
    # broadcast-best: every island receives the champion island's elites
    champ = jnp.argmin(fit_best)
    return (jnp.broadcast_to(elite_op[champ], elite_op.shape),
            jnp.broadcast_to(elite_arg[champ], elite_arg.shape))


def migrate_local(icfg: IslandConfig, new_op, new_arg, elite_op, elite_arg,
                  generation, fit_best):
    """In-device lowering of island migration.

    new_op/new_arg: int32[I, P, N] — the bred next generation.
    elite_op/elite_arg: int32[I, k, N] — each island's best k trees from
    the just-evaluated population (`island_elites`). fit_best: f32[I] —
    each island's best fitness this generation (broadcast-best routing).
    When a migration generation comes due every island's last k offspring
    slots are overwritten by the routed arrivals; otherwise the
    generation passes through unchanged (a branch-free select, so the
    compiled program is identical every generation)."""
    k = icfg.migrate_k
    if k <= 0 or new_op.shape[0] <= 1:
        return new_op, new_arg
    event_idx = generation // icfg.migrate_every
    inc_op, inc_arg = _route_local(icfg, elite_op, elite_arg, event_idx, fit_best)
    due = (generation % icfg.migrate_every) == (icfg.migrate_every - 1)
    new_op = jnp.where(due, new_op.at[:, -k:].set(inc_op), new_op)
    new_arg = jnp.where(due, new_arg.at[:, -k:].set(inc_arg), new_arg)
    return new_op, new_arg


def migrate_sharded(icfg: IslandConfig, new_op, new_arg, elite_op, elite_arg,
                    generation, fit_best, pod_axis: str | None, is_receiver):
    """Mesh lowering: pods × in-device islands (called inside shard_map).

    Shapes are per-shard: new_op/new_arg int32[I_local, P_local, N] (this
    model-rank's slice of the pod's local islands), elite_op/elite_arg
    int32[I_local, k, N] and fit_best f32[I_local] replicated within the
    pod (gathered population), so every rank performs identical
    collectives. `is_receiver` gates the overwrite to the model rank
    whose slice holds each island's last k offspring slots.

    Composition with the in-device lowering, per topology:

      ring            global ring in pod-major order: local islands roll
                      in-device; local island 0 receives the PREVIOUS
                      pod's last island via `ppermute`
      torus           grid = (pods × local islands): east = in-device
                      roll, south = `ppermute` of all local elites to
                      the next pod; events alternate
      broadcast-best  champion selected across ALL pods × islands
                      (`all_gather` of per-pod champions), broadcast
                      everywhere
    """
    k = icfg.migrate_k
    I_local = new_op.shape[0]
    n_pods = jax.lax.axis_size(pod_axis) if pod_axis else 1
    if k <= 0 or I_local * n_pods <= 1:
        return new_op, new_arg
    event_idx = generation // icfg.migrate_every
    perm = [(i, (i + 1) % n_pods) for i in range(n_pods)]

    if icfg.topology == "ring":
        inc_op = jnp.roll(elite_op, 1, axis=0)
        inc_arg = jnp.roll(elite_arg, 1, axis=0)
        if n_pods > 1:
            inc_op = inc_op.at[0].set(
                jax.lax.ppermute(elite_op[-1], pod_axis, perm))
            inc_arg = inc_arg.at[0].set(
                jax.lax.ppermute(elite_arg[-1], pod_axis, perm))
    elif icfg.topology == "torus":
        if n_pods > 1:
            east_op = jnp.roll(elite_op, 1, axis=0)
            east_arg = jnp.roll(elite_arg, 1, axis=0)
            if I_local == 1:
                # a 1-wide row degenerates east to the pod ring
                east_op = jax.lax.ppermute(elite_op, pod_axis, perm)
                east_arg = jax.lax.ppermute(elite_arg, pod_axis, perm)
            south_op = jax.lax.ppermute(elite_op, pod_axis, perm)
            south_arg = jax.lax.ppermute(elite_arg, pod_axis, perm)
            alt = event_idx % 2 == 0
            inc_op = jnp.where(alt, east_op, south_op)
            inc_arg = jnp.where(alt, east_arg, south_arg)
        else:
            inc_op, inc_arg = _route_local(icfg, elite_op, elite_arg,
                                           event_idx, fit_best)
    else:  # broadcast-best
        champ = jnp.argmin(fit_best)
        c_op, c_arg, c_fit = elite_op[champ], elite_arg[champ], fit_best[champ]
        if n_pods > 1:
            pods_fit = jax.lax.all_gather(c_fit, pod_axis)  # [n_pods]
            pods_op = jax.lax.all_gather(c_op, pod_axis)  # [n_pods, k, N]
            pods_arg = jax.lax.all_gather(c_arg, pod_axis)
            g = jnp.argmin(pods_fit)
            c_op, c_arg = pods_op[g], pods_arg[g]
        inc_op = jnp.broadcast_to(c_op, elite_op.shape)
        inc_arg = jnp.broadcast_to(c_arg, elite_arg.shape)

    due = ((generation % icfg.migrate_every) == (icfg.migrate_every - 1)) & is_receiver
    new_op = jnp.where(due, new_op.at[:, -k:].set(inc_op), new_op)
    new_arg = jnp.where(due, new_arg.at[:, -k:].set(inc_arg), new_arg)
    return new_op, new_arg


def migrate(cfg, op_local, arg_local, elite_op, elite_arg, generation,
            pod_axis: str, is_receiver):
    """Legacy pod-axis ring lowering (islands=1 runs with pop sharded over
    pods; called inside shard_map). Kept bit-for-bit: the pod slices ARE
    the islands, one per pod, and every `migrate_every` generations each
    pod's `migrate_k` best trees ride a ring `collective_permute` to the
    next pod, replacing offspring slots there.

    op_local/arg_local: int32[P_local, N] — this device's slice of the NEW
    generation. elite_op/elite_arg: int32[k, N] — this pod's best k trees
    from the just-evaluated population (replicated within the pod, so
    every model-rank performs an identical permute). The receiving rank
    (`is_receiver`, one per pod) overwrites its last k offspring slots
    when a migration generation comes due.
    """
    n_pods = jax.lax.axis_size(pod_axis)
    if n_pods <= 1:
        return op_local, arg_local
    k = cfg.migrate_k
    perm = [(i, (i + 1) % n_pods) for i in range(n_pods)]
    mig_op = jax.lax.ppermute(elite_op, pod_axis, perm)
    mig_arg = jax.lax.ppermute(elite_arg, pod_axis, perm)

    due = ((generation % cfg.migrate_every) == (cfg.migrate_every - 1)) & is_receiver
    new_op = jnp.where(due, op_local.at[-k:].set(mig_op), op_local)
    new_arg = jnp.where(due, arg_local.at[-k:].set(mig_arg), arg_local)
    return new_op, new_arg
