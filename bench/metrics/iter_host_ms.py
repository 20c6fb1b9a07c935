"""iter_host_ms (engine): host time per scheduler iteration.

Mean, over the harness's ``bench.engine_iter`` spans (one per
``Engine.run`` call) in the traced window, of the span's length minus the
time inside it in which an operation ran on the device."""


def read(ctx):
    red = ctx.reduction
    spans = red.host_spans("bench.engine_iter")
    if not spans:
        return None
    host = [(b - a) - red.busy_between(a, b) for a, b in spans]
    return sum(host) / len(host) / 1e6
