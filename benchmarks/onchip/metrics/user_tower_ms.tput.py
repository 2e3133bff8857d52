"""Device ms per serve flush inside the ``user_tower`` scope: the
history and user-id lookups and the user tower MLPs
(retriever.serve_stage_rank)."""
from trace_reduce import ms_per_run


def read(ctx):
    return ms_per_run(ctx["trace"], ctx["module"], ("user_tower",))
