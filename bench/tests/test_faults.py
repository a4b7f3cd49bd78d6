"""A run with the timed path broken underneath comes out not correct:
the harness's look for a chip is skipped and the rest of a run is
driven at a small size, once for each fault the cell can have."""
import pytest

import run


def _run(cell, control=False):
    result, _ = run.run_cell(cell.name, 2**31 + 11, 1.0, False,
                             require_tpu=False, cell=cell, control=control)
    return result


def _state_unchanged(monkeypatch, engine):
    monkeypatch.setattr(engine, "_step_body_any",
                        lambda cfg, state, X, y, weight: state)


def _half_rows(monkeypatch, engine):
    """Half of the rows left out of the fitness, the rest scored."""
    real = engine._eval_fitness

    def half(cfg, op, arg, X, y, weight, const_table):
        n = X.shape[-1] // 2
        return real(cfg, op, arg, X[:, :n], y[:n],
                    None if weight is None else weight[:n], const_table)

    monkeypatch.setattr(engine, "_eval_fitness", half)


def _answers_swapped(monkeypatch, engine):
    """Each tree is published with another tree's fitness."""
    real = engine._eval_fitness
    monkeypatch.setattr(engine, "_eval_fitness",
                        lambda *a: real(*a)[::-1])


@pytest.mark.parametrize("workload", ["kat7-90k.tree-fit"])
def test_clean_fit_run_is_correct(workload, small_cell, fresh_jax):
    result = _run(small_cell(workload))
    assert result["correct"], result["checks"]


@pytest.mark.parametrize("fault", [_state_unchanged, _half_rows,
                                   _answers_swapped])
@pytest.mark.parametrize("workload", ["kat7-90k.tree-fit"])
def test_fit_fault_is_caught(workload, fault, small_cell, fresh_jax,
                             monkeypatch):
    from repro.core import engine

    fault(monkeypatch, engine)
    assert not _run(small_cell(workload))["correct"]


@pytest.mark.parametrize("workload", ["kat7-90k.tree-fit"])
def test_control_is_not_correct(workload, small_cell, fresh_jax):
    """The reference in bfloat16, put in the program's place, fails the
    same checks a run is judged by."""
    result = _run(small_cell(workload), control=True)
    assert not result["correct"], result["checks"]
    gap = result["checks"]["fitness_gap"]
    assert gap["value"] > gap["limit"]
