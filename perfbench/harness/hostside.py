"""What the host did during the window, for the run's earlier lines: a run
that lands 3 % off says whether the process burnt more CPU seconds (slower
cores, a shared cache), got fewer of them (other tenants: the load average)
or ran more passes of the cyclic GC. Nothing here enters a metric."""

from __future__ import annotations

import gc
import os


def _loadavg():
    try:
        with open("/proc/loadavg") as f:
            return float(f.read().split()[0])
    except (OSError, ValueError):
        return None


class Watch:
    """Reads counters the process keeps anyway, before and after: nothing
    runs inside the window on its account."""

    def __enter__(self):
        self._gc = [g["collections"] for g in gc.get_stats()]
        self.load = [_loadavg(), None]
        self._times = os.times()
        return self

    def __exit__(self, *exc):
        t = os.times()
        self.cpu_user_s = t.user - self._times.user
        self.cpu_sys_s = t.system - self._times.system
        self.wall_s = t.elapsed - self._times.elapsed
        self.load[1] = _loadavg()
        self.gc_passes = [g["collections"] - was
                          for g, was in zip(gc.get_stats(), self._gc)]
        return False

    def summary(self) -> dict:
        return {"cpu_user_s": self.cpu_user_s, "cpu_sys_s": self.cpu_sys_s,
                "wall_s": self.wall_s, "loadavg_1m_start_end": self.load,
                "gc_passes_by_generation": self.gc_passes}
