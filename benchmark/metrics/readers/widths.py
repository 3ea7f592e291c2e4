"""Reader of the solve ledger's evaluations priced at the widths the loop
ran (``photon_ml_tpu.optim.scheduler.solve_stats``), read from the
process-wide registry after the traced window as ``counters`` reads its
two counts. Returns nothing where the program keeps no such price or
recorded no solve under the metric's ``solve_label``."""


def width_evaluations_per_solve(ctx):
    """Mean over the recorded solves of the needed evaluations, each
    iteration's priced at the width the loop ran it at, in full-width
    evaluations: what the loop's line searches read of the lanes' data."""
    from photon_ml_tpu.optim.scheduler import solve_stats

    totals = getattr(solve_stats, "evaluation_totals", None)
    if totals is None:
        return None
    kinds = totals(ctx["metric"]["solve_label"]).values()
    solves = sum(t["solves"] for t in kinds)
    if not solves or not all("width_evaluations" in t for t in kinds):
        return None
    return sum(t["width_evaluations"] for t in kinds) / solves
