"""Layer: backend. Host-clock seconds per proof in which a `backend/msm*`
call was in flight: from the start of a `dispatch` span to the end of the
`wait` that follows it, by the program's own spans (harness/spans.py). An
upper bound on the MSM's device seconds, never a device metric."""
from harness import spans


def read(ctx):
    return spans.inflight_seconds(ctx, spans.MSM_WORDS)
