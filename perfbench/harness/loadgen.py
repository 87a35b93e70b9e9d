"""The one general load generator. A traffic mix is a file of parameters
(perfbench/traffic/<name>.json):

    clients      how many clients send at once, each its next request when
                 its last is served (closed loop), over a connection that
                 genEvmProof_* holds until the proof is there (blocking)
    concurrency  the service's `--concurrency`
    stagger_s    optional: client number k (from 0) sends its first request
                 k x this many seconds after the first send, so that the
                 jobs of a closed loop run out of phase, as a backlog's do
                 once it has been going for a while, and do not all start
                 in the same millisecond (0 where the key is absent)

Requests are sent while less than `seconds` have passed since the first
send; every request sent is waited for; the window ends when the last one
is served. Nothing is abandoned. An open loop at a fixed rate and
submitProof_* with polling come with the first cell that sends such
traffic."""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field


@dataclass
class Sent:
    index: int
    request: dict                    # as the requests/ file made it
    t_send: float = 0.0
    t_done: float = 0.0
    result: dict | None = None
    error: str | None = None
    job_id: str | None = None
    manifest: dict | None = None
    spans: list = field(default_factory=list)


class Window:
    def __init__(self, served, traffic: dict, make_request, methods,
                 seconds: float):
        self.served = served
        self.traffic = traffic
        self.make_request = make_request      # index -> request dict
        self.method = methods[0]
        self.seconds = float(seconds)
        self.sent: list = []
        self._lock = threading.Lock()
        self._next = 0
        self.t_first = None
        self.t_last = None

    def _open(self) -> bool:
        return time.time() - self.t_first < self.seconds

    def _take(self) -> Sent:
        with self._lock:
            i = self._next
            self._next += 1
        s = Sent(index=i, request=self.make_request(i))
        with self._lock:
            self.sent.append(s)
        return s

    def _blocking(self, client, s: Sent):
        s.t_send = time.time()
        try:
            s.result = client._call(self.method, s.request["params"])
        except Exception as exc:      # a failed request is a failed operation
            s.error = f"{type(exc).__name__}: {exc}"
        s.t_done = time.time()

    def _client(self, client, k: int):
        delay = k * float(self.traffic.get("stagger_s", 0.0))
        if delay:
            time.sleep(max(0.0, self.t_first + delay - time.time()))
        while self._open():
            self._blocking(client, self._take())

    def run(self):
        self.t_first = time.time()
        threads = [threading.Thread(target=self._client,
                                    args=(self.served.client(), k))
                   for k in range(int(self.traffic.get("clients", 1)))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        self.t_last = max([s.t_done for s in self.sent] + [self.t_first])
        self.sent.sort(key=lambda s: s.index)
        return self

    @property
    def wall_s(self) -> float:
        return self.t_last - self.t_first

    @property
    def ok(self) -> list:
        return [s for s in self.sent if s.error is None and s.result]


def attach_records(client, method_submit: str, sent: list):
    """Job id, manifest and span tree of each served request, read once the
    window has closed. A blocking call hides its job id: the same witness
    dedups onto the finished job."""
    for s in sent:
        if s.error is not None:
            continue
        try:
            if s.job_id is None:
                s.job_id = client._call_shedding(
                    method_submit, dict(s.request["params"]))["job_id"]
            s.manifest = client.get_manifest(s.job_id)
            s.spans = client.get_trace(s.job_id).get("traceEvents", [])
        except Exception as exc:
            s.error = f"records unavailable: {type(exc).__name__}: {exc}"
