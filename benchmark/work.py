"""What the algorithm needs, from shapes and iteration counts alone: the
numerators of ``fe_pass_roofline`` and ``sweep_mfu``. Work the program does
beyond this (extra line-search trials, padded bucket lanes, relayouts) is not
counted, so it lowers the share instead of raising it."""

from __future__ import annotations


def fe_pass_bytes(rows: int, features: int, iterations: int,
                  itemsize: int = 4) -> int:
    """Bytes the dense fixed-effect solve has to move: one value-and-gradient
    evaluation at the start and one per L-BFGS iteration, each reading X
    twice (margins X w, gradient X^T r). The n-vectors (labels, offsets,
    residuals) are left out: 3 of 67 columns."""
    return (iterations + 1) * 2 * rows * features * itemsize


def solve_evaluations(task: str, features: int, cap: int) -> int:
    """Evaluations one per-entity solve is counted at: the iteration cap for
    the logistic loss (a vmapped wave runs until its slowest lane stops, and
    the program reports no iterations per lane, so the count is the cap's,
    not a measured one), and the conjugate-direction bound of a
    ``features``-wide quadratic for the squared loss."""
    if task == "logistic":
        return cap + 1
    if task == "linear":
        return min(cap, features) + 1
    raise ValueError(f"unknown task {task!r}: no evaluation count for it")


def trained_rows(counts, max_samples) -> int:
    """Rows a random-effect coordinate trains on: an entity with more than
    ``max_samples`` rows trains on that many (all its rows are scored)."""
    if max_samples is None:
        return int(sum(counts))
    return int(sum(min(int(c), int(max_samples)) for c in counts))


def sweep_flops(task: str, rows: int, fixed_features: int,
                fixed_iterations: int, tables: list, cap: int) -> int:
    """FLOPs one descent sweep needs: per evaluation 2nd for the margins and
    2nd for the gradient over the rows trained on, and 2nd for each
    coordinate's rescoring of all rows. ``tables`` holds one (features,
    trained rows) pair per random-effect coordinate."""
    flops = (fixed_iterations + 1) * 4 * rows * fixed_features
    flops += 2 * rows * fixed_features
    for d, trained in tables:
        flops += solve_evaluations(task, d, cap) * 4 * trained * d
        flops += 2 * rows * d
    return flops
