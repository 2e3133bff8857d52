"""Real rows per micro-batcher flush (served_rows / n_flushes)."""


def read(ctx):
    b = ctx["batcher"]
    return b.served_rows / b.n_flushes if b.n_flushes else None
