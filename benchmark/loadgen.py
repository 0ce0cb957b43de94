"""Out-of-process open-loop load generator over raw sockets.

Each worker process owns a few connections to one node. Every request is
built before the start, sent when it falls due whatever is still
outstanding (an open loop: independent users), and timed from its due time,
so a stall counts against every request queued behind it. The worker
records how late it sent each request. Once its last request is answered,
or a minute past the window's close, it compares every GET answer with the
value the schedule says the key holds (one key's requests stay on one
connection, in order, so each GET has exactly one right answer).

Workers are started with the ``spawn`` method and import neither JAX nor
the program: the node's Python threads do not share their interpreter.
"""

from __future__ import annotations

import multiprocessing as mp
import selectors
import socket
import time

import numpy as np

# Per-request status.
OK, ERROR, WRONG, MISSING = 0, 1, 2, 3


def _worker(pipe, port: int, config: dict, seed: int, conns: list) -> None:
    from benchmark.data import Dataset

    ds = Dataset(config, seed)
    keys = ds.keys
    plans = []
    for sched in conns:
        kind, key, ver = sched["kind"], sched["key"], sched["ver"]
        vals = ds.values_at(key, ver)
        reqs = [
            (b"SET " + keys[k] + b" " + v + b"\r\n") if op
            else (b"GET " + keys[k] + b"\r\n")
            for op, k, v in zip(kind.tolist(), key.tolist(), vals)
        ]
        plans.append(reqs)
    socks = []
    for _ in conns:
        s = socket.create_connection(("127.0.0.1", port), timeout=30)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.setblocking(False)
        socks.append(s)
    pipe.send("ready")
    t0, deadline = pipe.recv()
    try:
        results = _run(socks, conns, plans, t0, deadline)
    finally:
        for s in socks:
            s.close()
    # Compare every GET answer, now that the window has closed.
    for sched, res in zip(conns, results):
        answers = res.pop("answers")
        for j, line in answers.items():
            want = b"VALUE " + ds.value(int(sched["key"][j]),
                                        int(sched["ver"][j]))
            if line != want:
                res["status"][j] = WRONG
                res.setdefault("wrong", []).append(
                    (int(sched["key"][j]), int(sched["ver"][j]),
                     line[:48].decode("latin-1")))
    pipe.send(results)
    pipe.close()


def _run(socks, conns, plans, t0: float, deadline: float) -> list[dict]:
    n_conn = len(socks)
    due = [t0 + c["due"] for c in conns]
    kind = [c["kind"] for c in conns]
    total = [len(p) for p in plans]
    sent_t = [np.full(t, np.nan) for t in total]
    done_t = [np.full(t, np.nan) for t in total]
    status = [np.full(t, MISSING, np.int8) for t in total]
    answers = [dict() for _ in range(n_conn)]
    nxt = [0] * n_conn
    got = [0] * n_conn
    wbuf = [bytearray() for _ in range(n_conn)]
    rbuf = [bytearray() for _ in range(n_conn)]
    sel = selectors.DefaultSelector()
    for i, s in enumerate(socks):
        sel.register(s, selectors.EVENT_READ, i)
    writing = [False] * n_conn
    clock = time.monotonic
    while True:
        now = clock()
        for i in range(n_conn):
            d, j, p = due[i], nxt[i], plans[i]
            n = total[i]
            while j < n and d[j] <= now:
                wbuf[i] += p[j]
                sent_t[i][j] = now
                j += 1
            nxt[i] = j
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
        if all(got[i] == total[i] for i in range(n_conn)) or now > deadline:
            break
        nexts = [due[i][nxt[i]] for i in range(n_conn) if nxt[i] < total[i]]
        timeout = min(max(0.0, min(nexts) - now), 0.002) if nexts else 0.002
        for key, mask in sel.select(timeout):
            i = key.data
            if not mask & selectors.EVENT_READ:
                continue
            try:
                data = socks[i].recv(1 << 20)
            except BlockingIOError:
                continue
            if not data:
                raise ConnectionError("server closed a load connection")
            t = clock()
            buf = rbuf[i]
            buf += data
            pos = 0
            j = got[i]
            while True:
                end = buf.find(b"\r\n", pos)
                if end < 0:
                    break
                line = bytes(buf[pos:end])
                pos = end + 2
                done_t[i][j] = t
                if line.startswith(b"ERROR"):
                    status[i][j] = ERROR
                elif kind[i][j] == 0:
                    status[i][j] = OK
                    answers[i][j] = line
                else:
                    status[i][j] = OK if line == b"OK" else ERROR
                j += 1
            got[i] = j
            del buf[:pos]
    sel.close()
    return [
        {"sent": sent_t[i], "done": done_t[i], "status": status[i],
         "answers": answers[i]}
        for i in range(n_conn)
    ]


class LoadGenerator:
    """``processes`` x ``per_process`` connections to ``port``, driving one
    schedule split by connection."""

    def __init__(self, port: int, config: dict, seed: int, sched,
                 conn_of_op: np.ndarray, processes: int,
                 per_process: int) -> None:
        self.sched = sched
        self.conn_of_op = conn_of_op
        ctx = mp.get_context("spawn")
        self.procs, self.pipes = [], []
        self.index = []  # per process: per connection op indices
        for w in range(processes):
            conns, idxs = [], []
            for c in range(w * per_process, (w + 1) * per_process):
                ix = np.flatnonzero(conn_of_op == c)
                idxs.append(ix)
                conns.append({"due": sched.due[ix], "kind": sched.kind[ix],
                              "key": sched.key[ix], "ver": sched.ver[ix]})
            parent, child = ctx.Pipe()
            p = ctx.Process(target=_worker,
                            args=(child, port, config, seed, conns),
                            daemon=True, name=f"loadgen-{w}")
            p.start()
            child.close()
            self.procs.append(p)
            self.pipes.append(parent)
            self.index.append(idxs)

    def wait_ready(self, timeout: float = 300.0) -> None:
        for p in self.pipes:
            if not p.poll(timeout):
                raise TimeoutError("a load worker did not get ready")
            if p.recv() != "ready":
                raise RuntimeError("a load worker failed to start")

    def start(self, t0: float, deadline: float) -> None:
        for p in self.pipes:
            p.send((t0, deadline))

    def collect(self, timeout: float) -> dict:
        """Per-op arrays in schedule order: sent and done times (monotonic
        seconds, NaN where none) and status; plus wrong-answer samples."""
        m = len(self.sched)
        out = {"sent": np.full(m, np.nan), "done": np.full(m, np.nan),
               "status": np.full(m, MISSING, np.int8), "wrong": []}
        try:
            for p, idxs in zip(self.pipes, self.index):
                if not p.poll(timeout):
                    raise TimeoutError("a load worker never reported")
                for ix, res in zip(idxs, p.recv()):
                    out["sent"][ix] = res["sent"]
                    out["done"][ix] = res["done"]
                    out["status"][ix] = res["status"]
                    out["wrong"] += res.get("wrong", [])
        finally:
            self.close()
        return out

    def close(self) -> None:
        for p in self.procs:
            p.join(timeout=10)
            if p.is_alive():
                p.kill()
                p.join(timeout=10)
        for p in self.pipes:
            p.close()
