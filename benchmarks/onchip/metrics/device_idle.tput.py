"""Share of the measured window in which no operation ran on the
device, % (1 - busy / window, the window from the first submit on)."""


def read(ctx):
    busy = ctx["busy_s"]
    return 100.0 * (1.0 - busy / ctx["window_s"]) if busy > 0 else None
