"""Layer: kernels. Device seconds per proof of the MSM's programs, by the
program's own device timeline (harness/timeline.py): the manifest's
`device.by_kind.msm`, the stretches of the marks in `backend/msm*`'s
dispatch spans and in `ec.decode_points`. Below `msm_inflight_s`, which
holds the host's side of the same calls too."""
from harness import timeline


def read(ctx):
    return timeline.kind_seconds(ctx, "msm")
