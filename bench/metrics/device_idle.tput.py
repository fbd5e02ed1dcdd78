"""Share of the traced window in which the device runs no op: 1 − the
union of the ``XLA Ops`` intervals over the window, in percent; with
several chips the mean over them, each printed on its own line."""
from bench import trace


def read(ctx):
    red = ctx["trace"]
    shares = trace.idle_share(red)
    for dev, s in zip(red["devices"], shares):
        ctx["log"](f"idle {dev}: {100.0 * s}%")
    return 100.0 * sum(shares) / len(shares)
