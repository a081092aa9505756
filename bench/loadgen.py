"""Load generator: one child process, standard library only, never JAX.

The harness (`run.py`) holds the chip and serves the router; this process
drives it over loopback HTTP/1.1 keep-alive with its own small JSON client,
so the generator and the server's handlers never share one interpreter lock
and a change to the program's client cannot move the yardstick.

Protocol on stdin/stdout, one JSON object per line:

  in   the plan (router URL, control URL, graphs, schedule parameters)
  out  {"event": "ready", ...}         after the priming pass
  in   "go"
  out  {"event": "closed", ...}        when the window's time is up
  out  {"event": "result", ...}        once every request due in the window
                                       has been answered (or lost)

Arrivals are fixed by the plan and the traffic's schedule seed. An open
loop sends a fixed number of probes whose gaps are the quantiles of an
exponential distribution, shuffled. The templates are drawn by the
traffic's ``templates`` (``{"dist": "uniform"}``: each equally often;
``{"dist": "zipf", "exponent": s}``: the k-th of a seeded ranking about
1/k^s of the time, by fixed counts). With ``bursts`` (``{"on_s": a,
"off_s": b}``) the probes arrive only in the on-periods of that cycle, at
the rate that keeps the window's mean. A closed loop runs query streams,
each sending the templates in its own order. Latency is timed from the
scheduled send time in an open loop, and from the send in a closed one.
"""
from __future__ import annotations

import http.client
import json
import math
import random
import socket
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple
from urllib.parse import urlsplit

GRACE_S = 60.0   # how long past the close an answer is still waited for


class Client:
    """One keep-alive HTTP/1.1 connection with TCP_NODELAY."""

    def __init__(self, url: str):
        u = urlsplit(url)
        self.host, self.port = u.hostname, u.port
        self.conn: Optional[http.client.HTTPConnection] = None

    def _connect(self) -> http.client.HTTPConnection:
        conn = http.client.HTTPConnection(self.host, self.port,
                                          timeout=GRACE_S + 60)
        conn.connect()
        conn.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return conn

    def request(self, method: str, path: str, body: Optional[bytes] = None,
                etag: Optional[str] = None) -> Tuple[int, Optional[str], bytes]:
        headers = {"Accept": "application/json"}
        if body is not None:
            headers["Content-Type"] = "application/json"
        if etag:
            headers["If-None-Match"] = etag
        for attempt in (0, 1):
            if self.conn is None:
                self.conn = self._connect()
            try:
                self.conn.request(method, path, body=body or b"",
                                  headers=headers)
                resp = self.conn.getresponse()
                data = resp.read()
                return resp.status, resp.getheader("ETag"), data
            except (http.client.RemoteDisconnected, ConnectionResetError,
                    BrokenPipeError):
                # A keep-alive socket the server closed: reconnect once.
                self.close()
                if attempt:
                    raise
        raise AssertionError("unreachable")

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


def emit(obj: dict) -> None:
    sys.stdout.write(json.dumps(obj) + "\n")
    sys.stdout.flush()


class Generator:
    def __init__(self, plan: dict):
        self.plan = plan
        self.router = plan["router"]
        self.graphs: Dict[str, dict] = plan["graphs"]
        self.bodies = {g: json.dumps(v["body"]).encode()
                       for g, v in self.graphs.items()}
        self.seconds = float(plan["seconds"])
        # The schedule comes from the traffic's own seed, not the run's:
        # the order of heavy and light probes around the commits changes
        # the queueing far more than a second run of the same order does.
        self.rng = random.Random(plan["schedule_seed"])
        self.t0 = 0.0
        self.lock = threading.Lock()
        self.records: List[list] = []
        self.cost_bodies: Dict[str, tuple] = {}
        self.scheduled = 0
        # ETag caches per client: client index -> graph id -> tag.
        self.etags: List[Dict[str, str]] = [
            {} for _ in range(int(plan.get("clients", 1)))]
        self.writer_etags: Dict[str, str] = {}

    def now(self) -> float:
        return time.perf_counter() - self.t0

    def cost(self, client: Client, gid: str, etag: Optional[str]):
        status, tag, data = client.request("POST", "/cost", self.bodies[gid],
                                           etag)
        if status == 200 and tag not in self.cost_bodies:
            with self.lock:
                self.cost_bodies[tag] = (gid, data.decode())
        return status, tag

    # -- before the window ----------------------------------------------------

    def prime(self) -> dict:
        """Cost every graph once (so every shape compiles before the window)
        and fetch every dataset's tablestats. The writer always starts with
        the ETags; the probing clients only where the plan says ``prime``."""
        c = Client(self.router)
        warm = {}
        for gid in self.plan["probe_graphs"]:
            status, tag = self.cost(c, gid, None)
            if status == 200:
                warm[gid] = tag
        if self.plan["prime"]:
            for cache in self.etags:
                cache.update(warm)
        self.writer_etags = dict(warm)
        stats = {}
        for key in self.plan.get("tablestats", []):
            status, _, data = c.request("GET",
                                        f"/{key}/tablestats?mode=paper")
            stats[key] = json.loads(data) if status == 200 else status
        c.close()
        return stats

    # -- the window -----------------------------------------------------------

    def _open_schedule(self) -> List[Tuple[float, int, str]]:
        rate = float(self.plan["probe_rate"])
        n = max(int(round(rate * self.seconds)), 1)
        bursts = self.plan.get("bursts")
        on = float(bursts["on_s"]) if bursts else self.seconds
        cycle = on + float(bursts["off_s"]) if bursts else self.seconds
        # The on-time inside the window; arrivals are spread over it and
        # then laid out on the wall clock, cycle by cycle.
        span = (self.seconds // cycle) * on + min(self.seconds % cycle, on)
        gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
        self.rng.shuffle(gaps)
        total = sum(gaps) + sum(gaps) / n
        times, t = [], 0.0
        for g in gaps:
            t += g
            u = t * span / total
            times.append((u // on) * cycle + u % on)
        picks = self._template_picks(n)
        self.rng.shuffle(picks)
        clients = [i % len(self.etags) for i in range(n)]
        return list(zip(times, clients, picks))

    def _template_picks(self, n: int) -> List[str]:
        """``n`` template ids in the traffic's proportions (fixed counts by
        largest remainder, so every seed sends the same mix)."""
        graphs = list(self.plan["probe_graphs"])
        spec = self.plan.get("templates") or {"dist": "uniform"}
        if spec["dist"] == "uniform":
            return [graphs[i % len(graphs)] for i in range(n)]
        if spec["dist"] != "zipf":
            raise ValueError(f"unknown template distribution {spec['dist']!r}")
        self.rng.shuffle(graphs)
        w = [1.0 / (k + 1) ** float(spec["exponent"]) for k in range(len(graphs))]
        share = [n * x / sum(w) for x in w]
        counts = [int(x) for x in share]
        rest = sorted(range(len(graphs)), key=lambda k: counts[k] - share[k])
        for k in rest[:n - sum(counts)]:
            counts[k] += 1
        return [g for g, c in zip(graphs, counts) for _ in range(c)]

    def _sender(self, schedule, cursor) -> None:
        c = Client(self.router)
        while True:
            with self.lock:
                i = cursor[0]
                if i >= len(schedule):
                    break
                cursor[0] += 1
            t_sched, client, gid = schedule[i]
            wait = t_sched - self.now()
            if wait > 0:
                time.sleep(wait)
            self._probe(c, t_sched, client, gid)
        c.close()

    def _probe(self, c: Client, t_sched: float, client: int, gid: str) -> None:
        cache = self.etags[client]
        sent_tag = cache.get(gid)
        t_send = self.now()
        try:
            status, tag = self.cost(c, gid, sent_tag)
        except (OSError, http.client.HTTPException):
            c.close()
            status, tag = -1, None
        t_done = self.now()
        if status == 200:
            cache[gid] = tag
        with self.lock:
            self.records.append([t_sched, t_send, t_done, status, client,
                                 gid, sent_tag, tag])

    def _stream(self, stream: int) -> None:
        c = Client(self.router)
        order = list(self.plan["probe_graphs"])
        random.Random(f"{self.plan['schedule_seed']}/{stream}").shuffle(order)
        i = 0
        while self.now() < self.seconds:
            t = self.now()
            self._probe(c, t, stream, order[i % len(order)])
            i += 1
        c.close()

    def _writer(self, out: List[dict]) -> None:
        """Commit at a fixed rate; after each, probe until the plan is fresh."""
        router, control = Client(self.router), Client(self.plan["control"])
        rate = float(self.plan["commit_rate"])
        for w in self.plan["commits"]:
            t_sched = (w["index"] + 0.5) / rate
            if t_sched >= self.seconds:
                break
            wait = t_sched - self.now()
            if wait > 0:
                time.sleep(wait)
            rec = {"index": w["index"], "dataset": w["dataset"],
                   "graph": w["graph"], "t_sched": t_sched,
                   "t_start": self.now(), "stale": 0, "status": None}
            out.append(rec)
            try:
                status, _, data = control.request(
                    "POST", "/commit", json.dumps({"index": w["index"]}).encode())
                rec["commit"] = json.loads(data) if status == 200 else status
                status, _, data = router.request(
                    "POST", f"/{w['dataset']}/refresh", b"")
                rec["t_ack"] = self.now()
                rec["refresh"] = json.loads(data) if status == 200 else status
                old = self.writer_etags.get(w["graph"])
                while self.now() < self.seconds + GRACE_S:
                    status, tag = self.cost(router, w["graph"], old)
                    rec["status"] = status
                    if status == 200 and tag != old:
                        rec["t_fresh"] = self.now()
                        rec["etag"] = tag
                        self.writer_etags[w["graph"]] = tag
                        break
                    rec["stale"] += 1
                    time.sleep(0.01)
                status, _, data = router.request(
                    "GET", f"/{w['dataset']}/tablestats?mode=paper")
                rec["tablestats"] = json.loads(data) if status == 200 else status
            except (OSError, http.client.HTTPException) as e:
                rec["error"] = f"{type(e).__name__}: {e}"
                router.close()
                control.close()
        router.close()
        control.close()

    def run(self) -> dict:
        threads = []
        writes: List[dict] = []
        plan = self.plan
        self.t0 = time.perf_counter()
        if plan["loop"] == "open":
            schedule = self._open_schedule()
            self.scheduled = len(schedule)
            cursor = [0]
            threads += [threading.Thread(target=self._sender,
                                         args=(schedule, cursor))
                        for _ in range(int(plan["senders"]))]
        else:
            threads += [threading.Thread(target=self._stream, args=(s,))
                        for s in range(int(plan["streams"]))]
        if plan.get("commits"):
            threads.append(threading.Thread(target=self._writer,
                                            args=(writes,)))
        for t in threads:
            t.daemon = True
            t.start()
        time.sleep(max(self.seconds - self.now(), 0.0))
        emit({"event": "closed", "t": self.now()})
        for t in threads:
            t.join(timeout=max(self.seconds + GRACE_S - self.now(), 0.1))
        lost = sum(t.is_alive() for t in threads)
        with self.lock:
            return {"event": "result", "records": list(self.records),
                    "scheduled": self.scheduled or len(self.records),
                    "writes": writes, "cost_bodies": dict(self.cost_bodies),
                    "lost_threads": lost, "window_s": self.seconds}


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    gen = Generator(plan)
    stats = gen.prime()
    emit({"event": "ready", "tablestats": stats})
    if sys.stdin.readline().strip() != "go":
        return 1
    emit(gen.run())
    return 0


if __name__ == "__main__":
    sys.exit(main())
