"""Output tokens made visible during the window over the window's length;
partly served requests count too."""


def read(ctx):
    lg = ctx.log
    n = sum(1 for v in lg.visible.values() for t in v if t <= lg.end)
    return n / lg.seconds
