"""Share of the window's device-idle time, %, under no program span
(``batcher.*``, ``serve.*``): what the program's own spans cannot yet
name.  The harness's spans do not count."""
import span_reduce


def read(ctx):
    return span_reduce.idle_unattributed_pct(ctx)
