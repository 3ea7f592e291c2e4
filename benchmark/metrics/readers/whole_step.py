"""Readers of the whole job's share of the chip's peaks: required work of
one job (the family's ``work``) over the time a job took."""


def _share(ctx, what, peak):
    job = (ctx.get("work") or {}).get("job")
    if not job or not ctx.get("train_s"):
        return None
    return 100.0 * job[what] / (ctx["train_s"] * ctx["peaks"][peak] * ctx["chips"])


def mfu(ctx):
    return _share(ctx, "flops", "flops_per_s")


def hbm_share(ctx):
    return _share(ctx, "bytes", "hbm_bytes_per_s")
