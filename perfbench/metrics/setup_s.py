"""Process start to the first send of the window: boot, native library,
SRS, proving key (keygen on a checkout's first run, a load after), the warm-up
prove and whatever it compiles."""


def read(ctx):
    return ctx["setup_s"]
