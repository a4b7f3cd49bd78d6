"""GP-as-a-service: packed-vs-solo parity, scheduling order, cancel,
fault-injected restart, the no-recompile pin and slot invariance.

The load-bearing test is parity: a job packed into the multi-tenant
island batch must publish the SAME champion as a solo islands=1
GPSession — bitwise, not approximately. That requires feeding the solo
session the service's padded slot buffers (zero-weight padded rows,
zero feature columns): f32 reductions round differently over different
buffer shapes, so "same data" means same bytes, and the session's
`ingest(..., sample_weight=)` exists exactly for this.
"""
import numpy as np
import pytest

import jax

from repro.gp import GPSession, OperatorMix
from repro.service import (CANCELLED, DONE, PENDING, GPService, JobSpec,
                           pack_order, slot_buffers)

POP, DEPTH, FEATS, DCAP = 16, 3, 2, 32
TOURN = 6


def _dataset(seed, rows):
    r = np.random.RandomState(seed)
    X = r.randn(rows, FEATS).astype(np.float32)
    y = (X[:, 0] * X[:, 1] + 0.5 * X[:, 0]).astype(np.float32)
    return X, y


def _jobs(n, kernels=("r", "mse", "pearson"), tourn=TOURN):
    mixes = (OperatorMix(), OperatorMix(0.05, 0.05, 0.05, 0.85),
             OperatorMix(0.2, 0.2, 0.2, 0.4))
    jobs = []
    for i in range(n):
        X, y = _dataset(i, 12 + 5 * (i % 5))
        jobs.append(JobSpec(
            X, y, kernel=kernels[i % len(kernels)], mix=mixes[i % len(mixes)],
            tourn_size=tourn, stop_fitness=0.3 if i in (2, 5) else None,
            generations=4 + i % 6, seed=i, name=f"job-{i}"))
    return jobs


def _spec(seed, rows, **kw):
    kw.setdefault("tourn_size", TOURN)
    kw.setdefault("seed", seed)
    return JobSpec(*_dataset(seed, rows), **kw)


def _service(**kw):
    kw.setdefault("slots", 3)
    kw.setdefault("pop_size", POP)
    kw.setdefault("max_depth", DEPTH)
    kw.setdefault("n_features", FEATS)
    kw.setdefault("data_cap", DCAP)
    kw.setdefault("kernels", ("r",))
    kw.setdefault("tourn_draw", TOURN)
    kw.setdefault("block_size", 3)
    return GPService(**kw)


# --- the acceptance test: packed == solo, bitwise --------------------------------


def test_parity_packed_vs_solo():
    """8 heterogeneous jobs (3 kernels, 3 operator mixes, ragged rows,
    unequal budgets, two with early-stop bars) through a 3-slot service
    — so the run spans multiple admission/eviction waves — each must
    publish the same champion expression, bitwise-equal best fitness and
    generation count as its own solo islands=1 session on the same
    padded buffers. And the whole run compiles exactly one program."""
    jobs = _jobs(8)
    svc = _service(kernels=("r", "mse", "pearson"), block_size=4)
    handles = [svc.submit(j) for j in jobs]
    svc.run()

    assert all(h.status == DONE for h in handles)
    assert svc.stats["compiles"] == 1, "admission/eviction must not recompile"
    assert svc.stats["admissions"] == 8 and svc.stats["evictions"] == 8
    assert svc.heartbeats.dead_workers() == []

    for h, j in zip(handles, jobs):
        Xs, ys, ws = slot_buffers(j, FEATS, DCAP)
        sess = GPSession(pop_size=POP, max_depth=DEPTH, kernel=j.kernel,
                         mix=j.mix, tourn_size=j.tourn_size, elitism=1,
                         stop_fitness=j.stop_fitness,
                         generations=j.generations, backend="jnp")
        sess.ingest(Xs.T, ys, sample_weight=ws)
        sess.init(key=jax.random.PRNGKey(j.seed))
        sess.evolve(j.generations)
        assert h.gens_done == int(sess.generation), j.name
        assert h.best_fitness == float(sess.state.best_fitness), j.name
        assert h.best_expression == sess.best_expression(), j.name
        assert len(h.history) == h.gens_done, j.name


# --- scheduling order ------------------------------------------------------------


def test_pack_order_fifo_and_lpt():
    jobs = [JobSpec(*_dataset(i, 16), generations=g, seed=i)
            for i, g in enumerate([5, 20, 10, 20])]
    from repro.service.job import JobHandle
    handles = [JobHandle(i, j) for i, j in enumerate(jobs)]
    assert [h.job_id for h in pack_order(handles, 3, "fifo")] == [0, 1, 2]
    # lpt: largest REMAINING budget first, job_id breaks the 20/20 tie
    assert [h.job_id for h in pack_order(handles, 3, "lpt")] == [1, 3, 2]
    handles[1].gens_done = 15  # 5 remaining now
    assert [h.job_id for h in pack_order(handles, 2, "lpt")] == [3, 2]
    with pytest.raises(ValueError, match="strategy"):
        pack_order(handles, 1, "sjf")


def test_single_slot_runs_jobs_in_submit_order():
    """slots=1 + FIFO: the slot's occupant sequence must be the submit
    order, observed at every block boundary via the fault hook."""
    occupancy = []

    def spy(i):
        occupancy.extend(h.job_id for _, h in svc.batch.occupied)

    svc = _service(slots=1, fault_hook=spy)
    handles = [svc.submit(_spec(i, 16, generations=4)) for i in range(3)]
    svc.run()
    assert all(h.status == DONE for h in handles)
    # strictly non-decreasing occupant ids == FIFO, one job at a time
    assert occupancy == sorted(occupancy)
    assert set(occupancy) == {0, 1, 2}


# --- cancel ----------------------------------------------------------------------


def test_cancel_pending_and_running():
    svc = _service(slots=1, block_size=3)
    running = svc.submit(_spec(0, 16, generations=9))
    queued = svc.submit(_spec(1, 16, generations=4))

    # pending cancel: immediate, never admitted
    assert svc.cancel(queued.job_id) is True
    assert queued.status == CANCELLED and queued.gens_done == 0

    # running cancel: honoured at the next block boundary, partial results
    svc._fault_hook = lambda i: svc.cancel(running.job_id) if i == 1 else None
    svc.run()
    assert running.status == CANCELLED
    assert 0 < running.gens_done < 9
    assert running.best_expression is not None
    assert svc.cancel(running.job_id) is False  # already finished
    assert svc.idle()


# --- fault-injected restart ------------------------------------------------------


def test_restart_replays_to_identical_results(tmp_path):
    """Kill the scheduler mid-queue (injected fault), restart from the
    newest committed checkpoint: every published result must be
    identical to a fault-free run — restarts are invisible."""
    jobs = _jobs(4, kernels=("r",))

    ref = _service()
    ref_handles = [ref.submit(j) for j in jobs]
    ref.run()

    boom = {2: True}

    def fault(i):
        if boom.pop(i, False):
            raise RuntimeError("injected scheduler failure")

    svc = _service(checkpoint_dir=str(tmp_path), checkpoint_every=1,
                   fault_hook=fault)
    handles = [svc.submit(j) for j in jobs]
    svc.run()

    assert svc.stats["restarts"] == 1
    for h, r in zip(handles, ref_handles):
        assert h.status == DONE
        assert h.gens_done == r.gens_done
        assert h.best_fitness == r.best_fitness
        assert h.best_expression == r.best_expression


def test_compile_error_raises_without_restarts(tmp_path):
    """A block the compiler refuses fails identically on every replay, so
    the restart policy must not retry it: it raises on the first attempt."""
    attempts = []

    class Refused:
        def lower(self, *args):
            attempts.append(1)
            raise RuntimeError("compiler refused the tenant block")

    svc = _service(checkpoint_dir=str(tmp_path), checkpoint_every=1)
    svc.submit(_spec(0, 16, generations=4))
    svc._block = Refused()
    with pytest.raises(RuntimeError, match="compiler refused"):
        svc.run()
    assert attempts == [1] and svc.stats["restarts"] == 0


# --- slot invariance & elastic resume --------------------------------------------


def test_slot_invariance():
    """The same job must publish identical results from any slot, next
    to any neighbour — including with heterogeneous tournament size and
    point-mutation rate, which only slot-invariant operand encoding can
    deliver."""
    target = _spec(7, 20, generations=6, tourn_size=3, point_rate=0.5,
                   name="target")
    results = []
    for fillers in ([_spec(1, 16, generations=8)],
                    []):  # slot 1 next to a filler, then slot 0 alone
        svc = _service(slots=2)
        handles = [svc.submit(f) for f in fillers]
        t = svc.submit(target)
        svc.run()
        assert all(h.status == DONE for h in handles + [t])
        results.append((t.best_fitness, t.best_expression, t.gens_done,
                        tuple(t.history)))
    assert results[0] == results[1]


def test_adopt_resumes_at_different_slot_count():
    """A snapshot taken mid-flight on a 2-slot service, adopted by a
    3-slot service, must finish with results identical to an
    uninterrupted run — elastic resume only varies the slot count."""
    jobs = _jobs(3, kernels=("r",))
    for j in jobs:
        j.stop_fitness = None
        j.generations = 8  # > 2 blocks of 3: nothing finishes pre-snapshot

    ref = _service(slots=2)
    ref_handles = [ref.submit(j) for j in jobs]
    ref.run()

    a = _service(slots=2)
    for j in jobs:
        a.submit(j)
    a.run(max_blocks=2)  # partial: both slots mid-budget, job 2 queued
    snap = a._make_snapshot()
    assert not a.idle()

    b = _service(slots=3)
    handles = [b.submit(j) for j in jobs]  # same ids, same order
    b.adopt(snap)
    b.run()
    for h, r in zip(handles, ref_handles):
        assert h.status == DONE
        assert h.gens_done == r.gens_done
        assert h.best_fitness == r.best_fitness
        assert h.best_expression == r.best_expression


# --- submit-time validation & the job surface ------------------------------------


def test_submit_validation():
    svc = _service()
    with pytest.raises(ValueError, match="rows"):
        svc.submit(JobSpec(*_dataset(0, DCAP + 1)))
    with pytest.raises(ValueError, match="features"):
        X, y = _dataset(0, 16)
        svc.submit(JobSpec(np.concatenate([X, X], axis=1), y))
    with pytest.raises(ValueError, match="kernel"):
        svc.submit(JobSpec(*_dataset(0, 16), kernel="mse"))  # not compiled in
    with pytest.raises(ValueError, match="tourn"):
        svc.submit(JobSpec(*_dataset(0, 16), tourn_size=TOURN + 1))


def test_jobspec_validation_and_poll():
    X, y = _dataset(0, 16)
    with pytest.raises(ValueError, match="rows"):
        JobSpec(X, y[:-1])
    with pytest.raises(ValueError, match="generations"):
        JobSpec(X, y, generations=0)
    with pytest.raises(ValueError, match="unknown fitness kernel"):
        JobSpec(X, y, kernel="no-such-kernel")

    svc = _service(slots=1)
    h = svc.submit(JobSpec(X, y, generations=3, tourn_size=TOURN,
                           name="polled"))
    snap = svc.poll(h.job_id)
    assert snap["status"] == PENDING and snap["gens_done"] == 0
    assert snap["name"] == "polled" and snap["budget"] == 3
    done = svc.result(h.job_id)  # drives the loop
    assert done is h and h.status == DONE
    assert svc.poll(h.job_id)["best_expression"] == h.best_expression
