"""RESP load processes for the ``open_resp`` driver.

Each process owns a fixed set of connections and never imports JAX or
the program: it speaks RESP itself.  Per phase it regenerates its
connections' schedules from the seed (``traffic/generator.py``), waits
for the start time the parent gives, writes each command when it falls
due (commands due together go out in one write per connection), and
reads the replies, which come back in order on each connection.  It
returns, per command, when it was written and when its reply came, both
in seconds from the phase start on the shared monotonic clock, and the
reply: 1 or 0 for ``:1`` / ``:0``, -1 for anything else, -2 for none.
"""

from __future__ import annotations

import collections
import selectors
import socket
import time

import numpy as np

from benchmark.traffic import generator as gen

NO_REPLY, BAD_REPLY = -2, -1


def wire(*args: bytes) -> bytes:
    """One command as a RESP multibulk frame."""
    out = [b"*%d\r\n" % len(args)]
    for a in args:
        out.append(b"$%d\r\n%s\r\n" % (len(a), a))
    return b"".join(out)


def _commands(sched: dict, names: list) -> list:
    return [wire(b"BF.ADD" if a else b"BF.EXISTS", names[f], b"%d" % int(k))
            for a, f, k in zip(sched["add"], sched["filt"],
                               sched["keys"][:, 0])]


def prepare(seed: int, tr: dict, names: list, seconds: float, phase: int,
            conns: list) -> dict:
    """Schedules and wire bytes of one phase, built before its start."""
    scheds = {c: gen.schedule(seed, tr, len(names), seconds, c, phase)
              for c in conns}
    due = np.concatenate([scheds[c]["due"] for c in conns])
    who = np.concatenate([np.full(len(scheds[c]["due"]), c) for c in conns])
    pos = np.concatenate([np.arange(len(scheds[c]["due"])) for c in conns])
    order = np.argsort(due, kind="stable")
    return {"cmds": {c: _commands(scheds[c], names) for c in conns},
            "due": due[order], "who": who[order].tolist(),
            "pos": pos[order].tolist()}


def drive(socks: dict, waiting: dict, ph: dict, t0: float, seconds: float,
          grace_s: float) -> dict:
    """Run one prepared phase on this process's connections.  ``waiting``
    holds, per connection, the commands still owed a reply; entries of an
    earlier phase that gave up on them are None, and their late replies
    are read and dropped."""
    conns = sorted(socks)
    cmds, due, who, pos = ph["cmds"], ph["due"], ph["who"], ph["pos"]
    sent = {c: np.full(len(cmds[c]), np.nan) for c in conns}
    done = {c: np.full(len(cmds[c]), np.nan) for c in conns}
    val = {c: np.full(len(cmds[c]), NO_REPLY, np.int8) for c in conns}
    out = {c: bytearray() for c in conns}
    inbuf = {c: b"" for c in conns}
    sel = selectors.DefaultSelector()
    for c in conns:
        sel.register(socks[c], selectors.EVENT_READ, c)
    first_bad = None
    n, p, left = len(due), 0, len(due)
    while left and time.monotonic() - t0 < seconds + grace_s:
        now = time.monotonic() - t0
        while p < n and due[p] <= now:
            c, i = who[p], pos[p]
            out[c] += cmds[c][i]
            sent[c][i] = now
            waiting[c].append(i)
            p += 1
        for c in conns:
            if out[c]:
                try:
                    k = socks[c].send(out[c])
                except BlockingIOError:
                    k = 0
                del out[c][:k]
        wait = 0.002 if p >= n else max(0.0, min(0.002, due[p] - now))
        for key, _ in sel.select(wait):
            c = key.data
            data = socks[c].recv(1 << 16)
            if not data:
                raise ConnectionError(f"server closed connection {c}")
            lines = (inbuf[c] + data).split(b"\r\n")
            inbuf[c] = lines.pop()
            t = time.monotonic() - t0
            for line in lines:
                i = waiting[c].popleft()
                if i is None:
                    continue
                done[c][i] = t
                if line == b":1":
                    val[c][i] = 1
                elif line == b":0":
                    val[c][i] = 0
                else:
                    val[c][i] = BAD_REPLY
                    first_bad = first_bad or line[:200].decode("latin-1")
                left -= 1
    sel.close()
    for c in conns:
        waiting[c] = collections.deque(None for _ in waiting[c])
    return {"sent": sent, "done": done, "val": val, "first_bad": first_bad}


def main(conn, port: int, conn_ids: list, seed: int, tr: dict,
         names: list) -> None:
    """Process body: connect, then serve phase requests from the parent
    until it sends None."""
    socks = {}
    for c in conn_ids:
        s = socket.create_connection(("127.0.0.1", port))
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        s.setblocking(False)
        socks[c] = s
    names = [n.encode() for n in names]
    waiting = {c: collections.deque() for c in socks}
    try:
        conn.send("connected")
        while True:
            msg = conn.recv()
            if msg is None:
                return
            seconds, phase, rate, grace_s = msg
            ph = prepare(seed, {**tr, "rate": rate}, names, seconds, phase,
                         sorted(socks))
            conn.send("ready")
            t0 = conn.recv()
            conn.send(drive(socks, waiting, ph, t0, seconds, grace_s))
    finally:
        for s in socks.values():
            s.close()
        conn.close()
