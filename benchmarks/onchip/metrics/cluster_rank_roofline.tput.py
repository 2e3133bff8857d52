"""Cluster rank's share of its roofline, %: the least time the chip
needs for the work the ``cluster_rank`` scope holds, over the scope's
device time per flush.  Both sides cover the same ops: where the scope
holds the codebook dot (a ``dot_general``), the work is the scores
against the codebook and their top-n (work.cluster_rank); where the dot
lies outside it, as its fusion carries another tf_op, only the top-n over
the scores (work.top_k).  Work is counted at the run's mean padded rows
per flush, as the scope's time is taken per flush."""
import work
from trace_reduce import ms_per_run, scope_primitives


def read(ctx):
    tr, module = ctx["trace"], ctx["module"]
    ms = ms_per_run(tr, module, ("cluster_rank",))
    if ms is None:
        return None
    cfg = ctx["cfg"]
    b = ctx["batcher"]
    rows = (b.served_rows + b.padded_rows) / b.n_flushes
    if "dot_general" in scope_primitives(tr, "cluster_rank", module):
        flops, bytes_ = work.cluster_rank(rows, cfg.n_clusters,
                                          cfg.embed_dim,
                                          cfg.clusters_per_query)
    else:
        flops, bytes_ = work.top_k(rows, cfg.n_clusters,
                                   cfg.clusters_per_query)
    least, _ = work.roofline_s(flops, bytes_, ctx["peaks"])
    return 100.0 * least / (ms / 1e3)
