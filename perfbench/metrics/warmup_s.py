"""Layer: prover state. Seconds from the service reporting healthy (boot
returned) to the window's first send: the warm-up prove, which builds or
loads the proving key first, and the collection after it; the part of
`setup_s` that a run's own work decides."""


def read(ctx):
    healthy, window = ctx.get("healthy"), ctx.get("window")
    if healthy is None or window is None or window.t_first is None:
        return None
    return window.t_first - healthy
