"""The plain reference: an expression parser and interpreter
(`interp`), the bounds a float32 program may reach (`interval`) and the
fitness definitions (`fitness`). Imports nothing of the program under
test."""
from reference.fitness import fitness, fitness_range, gap
from reference.interp import evaluate, parse
from reference.interval import bounds


def score(expression: str, X_rows, y, kernel: str, n_classes: int = 2,
          dtype: str = "float32") -> float:
    """Reference fitness of one published expression over (X_rows, y)."""
    return fitness(kernel, evaluate(parse(expression), X_rows, dtype), y,
                   n_classes)


def score_range(expression: str, X_rows, y, kernel: str,
                n_classes: int = 2):
    """The fitness range a float32 program may publish for one
    expression (`fitness_range`), or None for a kernel without one."""
    lo, hi, maybe_nan = bounds(parse(expression), X_rows)
    return fitness_range(kernel, lo, hi, maybe_nan, y, n_classes)


__all__ = ["bounds", "evaluate", "fitness", "fitness_range", "gap", "parse",
           "score", "score_range"]
