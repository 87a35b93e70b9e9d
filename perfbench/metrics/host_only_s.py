"""Layer: device. Seconds per proof of the job's root span outside every
in-flight stretch (harness/spans.py): the device had nothing of this job.
A lower bound on the device's idle time, by the host's clock."""
from harness import spans


def read(ctx):
    return spans.per_proof(ctx, spans.host_only_s)
