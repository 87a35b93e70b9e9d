"""Layer: device. Seconds of the window, per proof served, in which the
device had work of NO served job, by the device tracks of the jobs'
`getTrace` merged on the wall clock they share (harness/timeline.py):
`no_inflight_s.pair`, its bound below by the host's clock, with the
device's own stretches."""
from harness import timeline


def read(ctx):
    return timeline.window_idle_s(ctx)
