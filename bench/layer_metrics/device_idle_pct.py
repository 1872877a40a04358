"""device_idle_pct — Device layer (TPU v5e).

1 - busy / window over the traced window: busy is the union of the
intervals in which a device operation ran, the window runs from the first
to the last of the benchmark's own spans (``bench.harness.xtrace``)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or tr.window_s <= 0 or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
