"""Layer: device. Seconds per proof of the job's root span in which the
device had no work of the job, by the program's own device timeline
(harness/timeline.py): the manifest's `device.idle_s`, its `idle_by_span`
says where. `host_only_s` is its lower bound by the host's clock."""
from harness import timeline


def read(ctx):
    return timeline.idle_seconds(ctx)
