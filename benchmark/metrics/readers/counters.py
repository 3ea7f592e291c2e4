"""Readers of the counters the program keeps of itself, read from the
process-wide registry after the traced window (as the harness reads
``compile_stats``): the solve ledger
``photon_ml_tpu.optim.scheduler.solve_stats``, which prices the per-entity
solves in objective evaluations. Every job makes the same solves (the
warm-up and the traced jobs alike), so a mean per solve does not depend on
how many jobs the ledger saw. Each returns nothing where the program keeps
no such ledger or recorded no solve under the metric's ``solve_label``."""


def _solves(ctx):
    """(solves, summed totals) of the metric's label, one-shot and
    compacted together, or None."""
    from photon_ml_tpu.optim.scheduler import solve_stats

    totals = getattr(solve_stats, "evaluation_totals", None)
    if totals is None:
        return None
    kinds = totals(ctx["metric"]["solve_label"]).values()
    solves = sum(t["solves"] for t in kinds)
    if not solves:
        return None
    return solves, {key: sum(t[key] for t in kinds)
                    for key in ("needed_evaluations", "lane_means")}


def needed_evaluations_per_solve(ctx):
    """Mean over the recorded solves of the evaluations a vmapped loop runs
    when lanes that have stopped do not hold its line search."""
    found = _solves(ctx)
    return None if found is None else found[1]["needed_evaluations"] / found[0]


def lane_evaluations_per_solve(ctx):
    """Mean over the recorded solves of the lanes' own evaluations over the
    lanes: the floor of a loop that ran each lane only for itself."""
    found = _solves(ctx)
    return None if found is None else found[1]["lane_means"] / found[0]
