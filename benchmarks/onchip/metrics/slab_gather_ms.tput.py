"""Device ms per serve flush inside the ``slab_gather`` scope: the
probed clusters' offsets and counts, and the (B, C, L) bias slab the
Alg. 1 merge reads (retriever.serve_stage_merge)."""
from trace_reduce import ms_per_run


def read(ctx):
    return ms_per_run(ctx["trace"], ctx["module"], ("slab_gather",))
