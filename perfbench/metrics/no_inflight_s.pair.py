"""Layer: device. Seconds of the window, per proof served, in which no job
had a backend call in flight (harness/spans.py): by the host's clock the
device had nothing of ANY job. What two jobs at once exist to shrink: one
job's host work hides behind the other's device work exactly where this
falls below `host_only_s` of the one-client cell."""
from harness import spans


def read(ctx):
    return spans.no_inflight_s(ctx)
