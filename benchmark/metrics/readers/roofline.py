"""Reader of a solve program's share of its roofline: the least time the
chip could take for the program's required work (the larger of FLOPs over
peak FLOP/s and bytes over peak bytes/s; for these solves the bytes bind)
over the program's device time in the trace."""

from benchmark.trace_reduce import find_program


def program_share(ctx):
    trace, metric = ctx["trace"], ctx["metric"]
    need = (ctx.get("work") or {}).get(metric["program"])
    if not trace or not need:
        return None
    found = find_program(trace["programs"], metric["patterns"])
    if not found or found[0] <= 0:
        return None
    least = max(need["flops"] / ctx["peaks"]["flops_per_s"],
                need["bytes"] / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least * ctx["jobs"] / found[0]
