"""Device ms per serve flush inside the ``cand_gather`` scope: the
merged positions to candidate ids, their index embeddings and biases,
and the exact-score einsum (retriever.serve_stage_merge)."""
from trace_reduce import ms_per_run


def read(ctx):
    return ms_per_run(ctx["trace"], ctx["module"], ("cand_gather",))
