"""Readers of the device layer's metrics."""


def idle_share(ctx):
    trace = ctx["trace"]
    if not trace or trace["window_s"] <= 0 or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def peak_hbm_gib(ctx):
    peak = ctx.get("memory_peak_bytes")
    return None if not peak else peak / 2.0 ** 30


def launches_per_job(ctx):
    """Program launches in the whole trace over the jobs traced. The harness
    traces nothing but the window's jobs (the warm-up job has ended before the
    profiler starts, the outputs are read after it stops), and the window's
    edge on the device's clock would cut the first programs of a job off from
    run to run (PERF.md section 3)."""
    trace = ctx["trace"]
    if not trace or not trace["launches"] or not ctx["jobs"]:
        return None
    return trace["launches"] / ctx["jobs"]
