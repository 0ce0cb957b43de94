"""Out-of-process open-loop GETs against several replicas, for as long as
the driver runs, each answer checked against a timeline of versions.

Each worker process draws its own Poisson arrivals (rate / processes) from
the seed, in order, with a uniform key and one of its connections for each;
connection ``c`` reads from node ``c % len(ports)``, so every key is read
from every replica. A request is sent when it falls due whatever is still
outstanding and is timed from its due time. The workers stop sending at
the time the driver sets (``stop``) and wait for what is outstanding.

Answers are kept as a 64-bit hash of the reply line. Once the driver has
closed the window it sends each replica's timeline of changes: for every
change, the key, the version it installs, the time from which a read may
see it and the time from which a read must see it. A GET of key ``k`` sent
at ``g`` and answered at ``d`` may then return any version from the last
change that was certain before ``g`` to the last that was possible by
``d``; anything else is wrong.

Workers are started with the ``spawn`` method and import neither JAX nor
the program.
"""

from __future__ import annotations

import multiprocessing as mp
import selectors
import socket
import time

import numpy as np

_BLOCK = 4096  # arrivals drawn at a time


def _arrivals(rng, rate: float, t_from: float, n_keys: int, conns: int):
    """Endless blocks of (due, key, conn), due in seconds from the start."""
    t = t_from
    while True:
        due = t + np.cumsum(rng.exponential(1.0 / rate, _BLOCK))
        t = float(due[-1])
        yield (due, rng.integers(0, n_keys, _BLOCK, dtype=np.int64),
               rng.integers(0, conns, _BLOCK, dtype=np.int64))


def _worker(pipe, ports, config, seed, worker, rate, per_process, t_from,
            stop) -> None:
    from benchmark.data import Dataset

    ds = Dataset(config, seed)
    keys = ds.keys
    socks = []
    for c in range(per_process):
        port = ports[(worker * per_process + c) % len(ports)]
        s = socket.create_connection(("127.0.0.1", port), timeout=30)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.setblocking(False)
        socks.append(s)
    rng = np.random.default_rng([seed & ((1 << 64) - 1), 300 + worker])
    pipe.send("ready")
    t0, grace = pipe.recv()
    try:
        rec = _run(socks, keys, _arrivals(rng, rate, t_from, ds.n,
                                          per_process), t0, stop, grace)
    finally:
        for s in socks:
            s.close()
    node = np.array([(worker * per_process + c) % len(ports)
                     for c in range(per_process)], np.int8)[rec["conn"]]
    pipe.send("stopped")
    timelines, base = pipe.recv()
    pipe.send(_check(ds, rec, node, timelines, base, t0))
    pipe.close()


def _run(socks, keys, arrivals, t0: float, stop, grace: float) -> dict:
    """Send each request when due until ``stop.value``; then read what is
    outstanding for at most ``grace`` seconds. Per request: due, sent and
    done times (monotonic), key, connection, answer hash."""
    n_conn = len(socks)
    cols = {"due": [], "key": [], "conn": []}
    sent, done, ans = [], [], []
    outstanding = [[] for _ in range(n_conn)]  # request indices, in order
    wbuf = [bytearray() for _ in range(n_conn)]
    rbuf = [bytearray() for _ in range(n_conn)]
    sel = selectors.DefaultSelector()
    for i, s in enumerate(socks):
        sel.register(s, selectors.EVENT_READ, i)
    writing = [False] * n_conn
    block = next(arrivals)
    due_b, key_b, conn_b = (t0 + block[0]).tolist(), block[1], block[2]
    j = 0
    clock = time.monotonic
    stop_at = None
    while True:
        now = clock()
        if stop_at is None:
            limit = stop.value
            while due_b[j] <= now and due_b[j] < limit:
                i = len(sent)
                c, k = int(conn_b[j]), int(key_b[j])
                cols["due"].append(due_b[j])
                cols["key"].append(k)
                cols["conn"].append(c)
                sent.append(now)
                done.append(np.nan)
                ans.append(0)
                wbuf[c] += b"GET " + keys[k] + b"\r\n"
                outstanding[c].append(i)
                j += 1
                if j == len(due_b):
                    block = next(arrivals)
                    due_b = (t0 + block[0]).tolist()
                    key_b, conn_b = block[1], block[2]
                    j = 0
            if due_b[j] >= limit:
                stop_at = max(limit, now)
        for i in range(n_conn):
            if wbuf[i]:
                try:
                    k = socks[i].send(wbuf[i])
                    del wbuf[i][:k]
                except BlockingIOError:
                    pass
            want_w = bool(wbuf[i])
            if want_w != writing[i]:
                sel.modify(socks[i], selectors.EVENT_READ
                           | (selectors.EVENT_WRITE if want_w else 0), i)
                writing[i] = want_w
        if stop_at is not None and (
                not any(outstanding) or now > stop_at + grace):
            break
        timeout = 0.002
        if stop_at is None:
            timeout = min(max(0.0, due_b[j] - now), 0.002)
        for skey, mask in sel.select(timeout):
            i = skey.data
            if not mask & selectors.EVENT_READ:
                continue
            try:
                data = socks[i].recv(1 << 20)
            except BlockingIOError:
                continue
            if not data:
                raise ConnectionError("server closed a read connection")
            t = clock()
            buf = rbuf[i]
            buf += data
            pos = 0
            q = outstanding[i]
            taken = 0
            while True:
                end = buf.find(b"\r\n", pos)
                if end < 0:
                    break
                r = q[taken]
                done[r] = t
                ans[r] = hash(bytes(buf[pos:end]))
                pos = end + 2
                taken += 1
            del q[:taken]
            del buf[:pos]
    sel.close()
    return {"due": np.array(cols["due"]), "key": np.array(cols["key"],
                                                          np.int64),
            "conn": np.array(cols["conn"], np.int64),
            "sent": np.array(sent), "done": np.array(done),
            "hash": np.array(ans, np.int64)}


def _check(ds, rec, node, timelines, base, t0: float) -> dict:
    """Compare every answer with the versions its replica's timeline
    allows. ``timelines[x]`` holds arrays ``key``, ``ver``, ``possible``,
    ``certain`` sorted by key, then time (both clocks run in step within
    a key); ``base`` is every key's version before the first change."""
    answered = ~np.isnan(rec["done"])
    wrong = np.zeros(len(rec["key"]), bool)
    examples = []
    for x, tl in enumerate(timelines):
        mine = np.flatnonzero((node == x) & answered)
        k = rec["key"][mine]
        # Times from the window's start, so that the sums below keep their
        # precision (nanoseconds) over any run.
        g, d = rec["sent"][mine] - t0, rec["done"][mine] - t0
        possible, certain = tl["possible"] - t0, tl["certain"] - t0
        seg = np.searchsorted(tl["key"], k, "left")
        # A change counts for a read by its key and time at once: keys
        # scaled past every time of the run keep (key, time) in one order.
        span = 1.0 + 2.0 * max(np.max(np.abs(d), initial=0.0),
                               np.max(np.abs(certain), initial=0.0))
        hi = np.searchsorted(tl["key"] * span + possible, k * span + d,
                             "right") - seg
        lo = np.searchsorted(tl["key"] * span + certain, k * span + g,
                             "left") - seg
        vers = np.r_[tl["ver"], 0]
        ver = np.where(lo > 0, vers[seg + lo - 1], base[k])
        got = rec["hash"][mine]
        for r, v in enumerate(ds.values_at(k, ver)):
            if hash(b"VALUE " + v) == got[r]:
                continue
            # Later versions the read may have seen: changes lo+1 .. hi.
            if any(hash(b"VALUE " + ds.value(int(k[r]), int(vers[i])))
                   == got[r] for i in range(seg[r] + lo[r], seg[r] + hi[r])):
                continue
            wrong[mine[r]] = True
            if len(examples) < 5:
                examples.append((x, int(k[r]), int(ver[r]),
                                 int(hi[r] - lo[r]), g[r], d[r]))
    return {"due": rec["due"], "sent": rec["sent"], "done": rec["done"],
            "wrong": wrong, "examples": examples, "conn": rec["conn"],
            "node": node}


class ReadGenerator:
    """``processes`` x ``per_process`` connections spread over ``ports``."""

    def __init__(self, ports: list[int], config: dict, seed: int,
                 rate: float, processes: int, per_process: int,
                 t_from: float) -> None:
        ctx = mp.get_context("spawn")
        self.per_process = per_process
        self.stop = ctx.Value("d", float("inf"), lock=False)
        self.procs, self.pipes = [], []
        for w in range(processes):
            parent, child = ctx.Pipe()
            p = ctx.Process(target=_worker, args=(
                child, ports, config, seed, w, rate / processes, per_process,
                t_from, self.stop), daemon=True, name=f"readgen-{w}")
            p.start()
            child.close()
            self.procs.append(p)
            self.pipes.append(parent)

    def wait_ready(self, timeout: float = 300.0) -> None:
        for p in self.pipes:
            if not p.poll(timeout):
                raise TimeoutError("a read worker did not get ready")
            if p.recv() != "ready":
                raise RuntimeError("a read worker failed to start")

    def start(self, t0: float, grace: float) -> None:
        """Arrivals are due from ``t0 + t_from``; after the stop, what is
        outstanding is waited for ``grace`` seconds at most."""
        for p in self.pipes:
            p.send((t0, grace))

    def finish(self, stop_at: float, timeout: float) -> None:
        """No request falls due from ``stop_at`` on; wait until every
        worker has its last answer (or gave up on it)."""
        self.stop.value = stop_at
        for p in self.pipes:
            if not p.poll(timeout):
                raise TimeoutError("a read worker never stopped")
            if p.recv() != "stopped":
                raise RuntimeError("a read worker failed")

    def collect(self, timelines: list[dict], base: np.ndarray,
                timeout: float) -> dict:
        """Every request of every worker: due, sent and done times, and
        whether its answer was wrong; plus examples of wrong answers."""
        out = {"due": [], "sent": [], "done": [], "wrong": [],
               "examples": [], "conn": [], "node": []}
        try:
            for p in self.pipes:
                p.send((timelines, base))
            for p in self.pipes:
                if not p.poll(timeout):
                    raise TimeoutError("a read worker never reported")
                res = p.recv()
                for k in ("due", "sent", "done", "wrong", "node"):
                    out[k].append(res[k])
                out["conn"].append(res["conn"] + len(out["conn"])
                                   * self.per_process)
                out["examples"] += res["examples"]
        finally:
            self.close()
        for k in ("due", "sent", "done", "wrong", "conn", "node"):
            out[k] = np.concatenate(out[k])
        return out

    def close(self) -> None:
        self.stop.value = -1.0
        for p in self.procs:
            p.join(timeout=10)
            if p.is_alive():
                p.kill()
                p.join(timeout=10)
        for p in self.pipes:
            p.close()
