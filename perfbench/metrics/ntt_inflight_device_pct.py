"""Layer: kernels. In the replay session, on the profiler's clock: of the
seconds the replayed NTT-kind calls were in flight (the program's `dispatch`
and `wait` annotations), the share in which a program ran on the device
(`XLA Modules` events). Says how far in-flight seconds overstate device
seconds. None where no such call was replayed."""
from harness import spans


def read(ctx):
    return spans.replay_inflight_device_pct(ctx, spans.NTT_WORDS)
