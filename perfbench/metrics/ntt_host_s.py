"""Layer: backend. Host-clock seconds per proof in the `encode` and `decode`
spans of the NTT-kind backend calls: limb split and upload, the upload
between a call's two crossings, the limb join after the last read."""
from harness import spans


def read(ctx):
    return spans.host_stage_seconds(ctx, spans.NTT_WORDS)
