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
    trace = ctx["trace"]
    if not trace or not trace["launches"] or not ctx["jobs"]:
        return None
    return trace["launches"] / ctx["jobs"]
