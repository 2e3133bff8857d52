"""Device ms per serve flush inside the Alg. 1 merge scope
(merge_serve, or fused_gather_rank on the fused path)."""
from trace_reduce import ms_per_run


def read(ctx):
    return ms_per_run(ctx["trace"], ctx["module"],
                      ("merge_serve", "fused_gather_rank"))
