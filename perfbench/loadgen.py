"""The one general load generator: a traffic file's parameters in, samples out.

Runs in the launcher, on a few threads, one kept-alive HTTP connection each.
The sequence of queries (template and parameters) is drawn from the seed
before the window opens: every seed sends the same templates in the same
proportions, in another order and with other parameters.

closed: `clients` threads, each sends its next query when its last one
returns. open: `rate * seconds` arrivals on a schedule fixed before the window
opens (`poisson` or `uniform`, see `arrival_times`), sent by a pool of
`senders` threads; latency counts from the instant a query was due, and how
late it left is kept.
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from dataclasses import dataclass, field
from urllib.parse import urlparse

import numpy as np


@dataclass
class Sample:
    index: int
    template: str
    params: dict
    sql: str
    due: float = 0.0  # seconds from the window's start
    sent: float = 0.0
    done: float = 0.0
    error: str | None = None
    doc: dict | None = field(default=None, repr=False)

    @property
    def latency_ms(self) -> float:
        return (self.done - self.due) * 1e3


def draw_queries(templates: dict, weights: dict[str, float], rng: np.random.Generator, count: int) -> list[Sample]:
    """`count` queries: whole rounds of the mix's templates (each as often as
    its weight says), every round shuffled, every query with its own parameters."""
    smallest = min(weights.values())
    round_names = [n for n, w in weights.items() for _ in range(max(1, round(w / smallest)))]
    out: list[Sample] = []
    while len(out) < count:
        for i in rng.permutation(len(round_names)):
            name = round_names[i]
            params = templates[name].draw(rng)
            out.append(Sample(len(out), name, params, templates[name].render(params)))
    return out[:count]


def arrival_times(loop: dict, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """When each of the `rate * seconds` queries of an open loop is due.

    `poisson`: exponential gaps, made steady from seed to seed. The window is
    cut into stretches of `levelSeconds` (default 1); each stretch gets the
    exponential distribution's own quantiles, one of each of its equal
    slices, in an order drawn from the seed. So every seed offers the same
    load in every stretch, with the same gaps and another sequence of bursts.
    Independent draws moved the offered rate by 5 % from seed to seed and the
    median latency with it, and one unbroken shuffle still let a seed's order
    pile a backlog that took seconds to drain (PERF.md, PR 23).
    `uniform`: equal gaps.
    """
    n = int(round(loop["rate"] * seconds))
    if loop.get("arrivals", "poisson") == "poisson":
        per = max(1, int(round(loop["rate"] * float(loop.get("levelSeconds", 1.0)))))
        quantiles = -np.log1p(-(np.arange(per) + 0.5) / per)
        gaps = np.concatenate([quantiles[rng.permutation(per)] for _ in range(-(-n // per))])[:n]
    else:
        gaps = np.ones(n)
    due = np.cumsum(gaps)
    return (due - due[0]) * (seconds / due[-1])  # the first is due at 0, the last inside the window


class Client:
    def __init__(self, broker_url: str, timeout_ms: int):
        u = urlparse(broker_url)
        self.host, self.port = u.hostname, u.port
        self.timeout_ms = timeout_ms
        self.conn: http.client.HTTPConnection | None = None

    def send(self, sql: str) -> dict:
        body = json.dumps({"sql": f"SET timeoutMs={self.timeout_ms}; {sql}"}).encode()
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(self.host, self.port, timeout=self.timeout_ms / 1e3 + 10)
            try:
                self.conn.request("POST", "/query/sql", body, {"Content-Type": "application/json"})
                rsp = self.conn.getresponse()
                raw = rsp.read()
                if rsp.status != 200:
                    raise RuntimeError(f"HTTP {rsp.status}: {raw[:200]!r}")
                return json.loads(raw)
            except (http.client.HTTPException, ConnectionError) as e:
                self.close()
                if attempt or not isinstance(e, (http.client.RemoteDisconnected, ConnectionResetError)):
                    raise
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def _run_one(client: Client, s: Sample, t0: float) -> None:
    s.sent = time.perf_counter() - t0
    try:
        s.doc = client.send(s.sql)
        if s.doc.get("exceptions"):
            s.error = f"exceptions: {str(s.doc['exceptions'])[:300]}"
    except Exception as e:  # a failed query is a sample that failed, not a failed run
        s.error = f"{type(e).__name__}: {e}"
    s.done = time.perf_counter() - t0


def run_window(broker_url: str, traffic: dict, queries: list[Sample], seconds: float,
               rng: np.random.Generator, on_start=None) -> tuple[list[Sample], float]:  # fmt: skip
    """Drive `queries` for `seconds`; returns the samples that were issued
    (all of them waited for) and the window's start on perf_counter."""
    loop = traffic["loop"]
    timeout_ms = int(traffic.get("timeoutMs", 30_000))
    lock = threading.Lock()
    cursor = [0]
    issued: list[Sample] = []

    if loop["kind"] == "open":
        for s, due in zip(queries, arrival_times(loop, seconds, rng)):
            s.due = float(due)
        queries = queries[: int(round(loop["rate"] * seconds))]
        n_threads = int(loop.get("senders", 16))
    else:
        n_threads = int(loop["clients"])

    t0 = time.perf_counter() + 0.05  # every thread is up before the window opens

    def take() -> Sample | None:
        with lock:
            i = cursor[0]
            if i >= len(queries):
                return None
            s = queries[i]
            if loop["kind"] == "open":
                if s.due >= seconds:
                    return None
            elif time.perf_counter() - t0 >= seconds:
                return None
            cursor[0] = i + 1
            issued.append(s)
            return s

    def worker() -> None:
        client = Client(broker_url, timeout_ms)
        time.sleep(max(t0 - time.perf_counter(), 0))
        try:
            while True:
                s = take()
                if s is None:
                    return
                if loop["kind"] == "open":
                    wait = s.due - (time.perf_counter() - t0)
                    if wait > 0:
                        time.sleep(wait)
                else:
                    s.due = max(time.perf_counter() - t0, 0.0)
                _run_one(client, s, t0)
        finally:
            client.close()

    threads = [threading.Thread(target=worker, name=f"loadgen-{i}", daemon=True) for i in range(n_threads)]
    for t in threads:
        t.start()
    time.sleep(max(t0 - time.perf_counter(), 0))
    if on_start is not None:
        on_start(t0)
    for t in threads:
        t.join()
    return issued, t0
