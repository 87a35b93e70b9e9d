"""Layer: backend. Host-clock seconds per proof in the `encode` and `decode`
spans of `backend/msm*` calls: limb split and upload before the device has
anything of the call, Montgomery decoding after the last read."""
from harness import spans


def read(ctx):
    return spans.host_stage_seconds(ctx, spans.MSM_WORDS)
