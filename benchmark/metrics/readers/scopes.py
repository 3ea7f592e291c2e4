"""Readers of the metrics that come from the program's own names in the
trace: device scopes (``jax.named_scope``) and host spans, as
:mod:`benchmark.trace_scopes` reduces them. Each returns nothing where the
reduced trace lacks the keys (a program without the names, a reducer that
does not collect them) or the scope never ran."""


def _scoped(ctx):
    trace = ctx.get("trace") or {}
    return trace.get("scopes"), trace.get("scope_runs")


def executions_per_job(ctx):
    """Executions of the metric's ``scope`` over the jobs traced."""
    _, runs = _scoped(ctx)
    if not runs or not ctx.get("jobs"):
        return None
    count = runs.get(ctx["metric"]["scope"])
    return count / ctx["jobs"] if count else None


def seconds_per_execution(ctx):
    """Device seconds under the metric's ``scope`` (self time of every
    operation whose path holds it) over the executions of its ``per`` scope:
    the gather's seconds per evaluation of the objective."""
    scopes, runs = _scoped(ctx)
    if not scopes or not runs:
        return None
    seconds = scopes.get(ctx["metric"]["scope"], {}).get("total_s")
    count = runs.get(ctx["metric"]["per"])
    return seconds / count if seconds and count else None


def idle_unattributed_share(ctx):
    """Idle seconds of the window that no span of the program covers, over
    all idle seconds: whether the spans are complete."""
    gaps = (ctx.get("trace") or {}).get("host_gaps")
    if not gaps or gaps["idle_s"] <= 0:
        return None
    return 100.0 * gaps["unattributed_s"] / gaps["idle_s"]
