"""Target attention's share of its roofline, %: the least time the chip
needs for the attention's work (``attn_work`` of the configuration's
ranking model: the query, K and V projections, the logits and the
weighted sum, and their least bytes), over the ``rank_attn`` scope's
device time per flush.  Work is counted at the run's mean padded rows per
flush, as the scope's time is taken per flush."""
import rankers
import work
from trace_reduce import ms_per_run


def read(ctx):
    ms = ms_per_run(ctx["trace"], ctx["module"], ("rank_attn",))
    if ms is None:
        return None
    cfg, b = ctx["cfg"], ctx["batcher"]
    rows = (b.served_rows + b.padded_rows) / b.n_flushes
    flops, bytes_ = rankers.ranking_model(cfg.ranking).attn_work(rows, cfg)
    least, _ = work.roofline_s(flops, bytes_, ctx["peaks"])
    return 100.0 * least / (ms / 1e3)
