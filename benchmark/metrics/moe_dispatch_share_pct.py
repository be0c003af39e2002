"""moe_dispatch_share_pct (%): what routing costs beside the products:
device seconds under ``moe/route`` (router, top 8, renormalise),
``moe/dispatch`` (sort, gather) and ``moe/combine`` (back to token order,
weight, sum) as a share of all device seconds under ``moe/*``, over the
decode and the prefill programs' calls in the window."""
from benchmark import scope_ops


def read(r):
    parts = [scope_ops.both(r, "moe/" + p)
             for p in ("route", "dispatch", "combine")]
    whole = scope_ops.both(r, "moe")
    if whole is None or any(p is None for p in parts):
        return None
    beside = sum(p[0] for p in parts)
    if not whole[2] or whole[0] <= 0:
        return None
    r.note("moe_dispatch_share", route_s=parts[0][0], dispatch_s=parts[1][0],
           combine_s=parts[2][0], moe_s=whole[0], mixed_s=whole[1])
    return 100.0 * beside / whole[0]
