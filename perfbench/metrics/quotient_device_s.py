"""Layer: kernels. Device seconds per proof of the device quotient (the LDE
chunks of `quotient/extend/dispatch`, the folds of `quotient/expressions`,
the inverse transform), by the program's own device timeline
(harness/timeline.py): the manifest's `device.by_kind_lower.quotient`, each
stretch from its last enqueue. The upper reading would count the host's
dispatch of the folds (~1.1 s a proof) as the device's; the LDE chunks
queue behind each other, so for them the two readings agree within ~1 %.
Below `quotient_s`."""
from harness import timeline


def read(ctx):
    return timeline.kind_seconds(ctx, "quotient", "by_kind_lower")
