"""Drivers: set-up, warm-up, the measured window and the check, one per
``driver`` kind a traffic mix names.  A driver holds what the window
returned and compares it with ``reference.py`` afterwards; it never
judges its own answers.

Phases record their host-clock seconds in ``self.phases`` so the result
line can show where set-up goes.
"""

from __future__ import annotations

import collections
import contextlib
import multiprocessing
import queue
import threading
import time

import numpy as np

from benchmark import reference
from benchmark.traffic import generator as gen

WAIT_AFTER_S = 60.0  # answers due in the window may come this late


class Sut:
    """The system under test: the client a configuration deploys, and the
    RESP server in front of it where the traffic needs one."""

    def __init__(self, cfg: dict, resp: bool):
        import redisson_tpu
        from redisson_tpu import Config, codecs

        c = cfg["client"]
        conf = Config().set_codec(getattr(codecs, c["codec"])())
        self.client = redisson_tpu.create(conf.use_tpu_sketch(**c["tpu_sketch"]))
        self.server = None
        if resp:
            from redisson_tpu.serve.resp import RespServer

            self.server = RespServer(self.client, host="127.0.0.1", port=0)

    def close(self) -> None:
        if self.server is not None:
            self.server.close()
        self.client.shutdown()


class Driver:
    resp = False

    def __init__(self, seed: int, cfg: dict, tr: dict, ann):
        self.seed, self.cfg, self.tr, self.ann = seed, cfg, tr, ann
        self.m, self.k = reference.geometry(cfg["expected_insertions"],
                                            cfg["false_probability"])
        self.names = [cfg["name_format"].format(i)
                      for i in range(cfg["filters"])]
        self.phases = {}
        self.attempted = self.failed = 0
        self.ops = {"add_keys": 0, "contains_keys": 0}

    def close(self) -> None:
        pass

    def check(self) -> dict:
        """The program's answers against the reference's."""
        want, got = self.answers(self.k)
        return {"mismatches": int(np.count_nonzero(want != got)),
                "unanswered": self.failed, "compared": len(want)}

    def control(self) -> dict:
        """The control in the program's place: the reference with one
        probe fewer than the configuration states (k - 1, the gather a
        later change would be tempted to skip), on the same ops, judged
        by the same comparison."""
        want, _ = self.answers(self.k)
        ctl, _ = self.answers(self.k - 1)
        return {"mismatches": int(np.count_nonzero(want != ctl)),
                "unanswered": 0, "compared": len(want)}

    @contextlib.contextmanager
    def phase(self, name: str):
        t = time.perf_counter()
        yield
        self.phases[name] = self.phases.get(name, 0.0) + \
            time.perf_counter() - t

    def init_filters(self, sut: Sut) -> None:
        with self.phase("filters_init"):
            for n in self.names:
                if not sut.client.get_bloom_filter(n).try_init(
                        self.cfg["expected_insertions"],
                        self.cfg["false_probability"]):
                    raise RuntimeError(f"try_init({n}) found it existing")


# -- bulk: closed loop, one caller -------------------------------------------


class Bulk(Driver):
    def setup(self, sut: Sut) -> None:
        self.init_filters(sut)
        self.bf = sut.client.get_bloom_filter(self.names[0])
        with self.phase("fill"):
            self._pipelined_adds(gen.fill_batches(self.seed, self.tr,
                                                  self.cfg))

    def _pipelined_adds(self, batches, keep=None) -> int:
        """add_all_async with up to ``inflight`` in flight, in order."""
        live, n = collections.deque(), 0
        for keys in batches:
            if len(live) >= self.tr["inflight"]:
                out = live.popleft().result()
                if keep is not None:
                    keep.append(np.asarray(out))
            with self.ann("bench:add_all_async"):
                live.append(self.bf.add_all_async(keys))
            n += len(keys)
        while live:
            out = live.popleft().result()
            if keep is not None:
                keep.append(np.asarray(out))
        return n

    def warmup(self, sut: Sut) -> None:
        tr = self.tr
        if tr["op"] == "contains":
            with self.phase("traffic_gen"):
                self.calls = [gen.contains_call(self.seed, tr, self.cfg, c)
                              for c in range(tr["call_pool"])]
            with self.phase("warmup"):
                self.bf.contains_many(self.calls[0])
        # An add window needs no warm-up of its own: the fill ran the same
        # batch shape through the same pipeline.

    def window(self, sut: Sut, seconds: float) -> None:
        if self.tr["op"] == "contains":
            self._window_contains(seconds)
        else:
            self._window_adds(seconds)

    def _window_contains(self, seconds: float) -> None:
        self.results = []
        per_call = sum(len(b) for b in self.calls[0])
        t0 = time.perf_counter()
        while True:
            c = len(self.results)
            with self.ann("bench:contains_many"):
                self.results.append(
                    self.bf.contains_many(self.calls[c % len(self.calls)]))
            if time.perf_counter() - t0 >= seconds:
                break
        self.elapsed = time.perf_counter() - t0
        self.attempted = len(self.results)
        self.ops["contains_keys"] = per_call * len(self.results)

    def _window_adds(self, seconds: float) -> None:
        ahead = queue.Queue(maxsize=2 * self.tr["inflight"])
        stop = threading.Event()

        def produce():
            i = 0
            while not stop.is_set():
                b = gen.add_batch(self.seed, self.tr, self.cfg, i)
                while not stop.is_set():
                    try:
                        ahead.put(b, timeout=0.1)
                        break
                    except queue.Full:
                        pass
                i += 1

        th = threading.Thread(target=produce, daemon=True)
        th.start()
        # Let the generator run ahead before the clock starts.
        while not ahead.full():
            time.sleep(0.01)
        self.results = []
        self.gen_wait_s = 0.0
        t0 = time.perf_counter()

        def batches():
            while time.perf_counter() - t0 < seconds:
                t = time.perf_counter()
                b = ahead.get()
                self.gen_wait_s += time.perf_counter() - t
                yield b

        n = self._pipelined_adds(batches(), keep=self.results)
        self.elapsed = time.perf_counter() - t0
        stop.set()
        th.join()
        self.attempted = len(self.results)
        self.ops["add_keys"] = n

    def e2e(self) -> dict:
        n = self.ops["add_keys"] + self.ops["contains_keys"]
        return {"ops_per_s": n / self.elapsed}

    def answers(self, k: int):
        """(reference answers with k probes, the program's answers) over
        every checked op of the window."""
        tr, cfg = self.tr, self.cfg
        ref = reference.BloomReference(1, self.m, k)
        ref.prefix_adds(0, np.concatenate(list(
            gen.fill_batches(self.seed, tr, cfg))))
        if tr["op"] == "contains":
            want, got = [], []
            for c, call in enumerate(self.calls):
                ks = np.concatenate(call)
                w = ref.expected(0, ks, np.zeros(len(ks), bool),
                                 np.zeros(len(ks), np.int64),
                                 np.ones(len(ks), bool))
                for res in self.results[c::len(self.calls)]:
                    want.append(w)
                    got.append(np.concatenate(res))
            return np.concatenate(want), np.concatenate(got)
        ks = np.concatenate([gen.add_batch(self.seed, tr, cfg, i)
                             for i in range(len(self.results))])
        every = np.ones(len(ks), bool)
        want = ref.expected(0, ks, every, np.arange(len(ks)), every)
        return want, np.concatenate(self.results)


# -- open loops ----------------------------------------------------------------


class OpenLoop(Driver):
    """Requests due on a fixed schedule, each timed from its due time to
    its reply at the client; filter f belongs to producer f mod producers,
    so each filter's ops have one order the reference can replay."""

    def setup(self, sut: Sut) -> None:
        self.init_filters(sut)
        self.fill(sut)

    def fill(self, sut: Sut) -> None:
        self.bfs = [sut.client.get_bloom_filter(n) for n in self.names]
        with self.phase("fill"):
            futs = [bf.add_all_async(gen.pool_fill(self.seed, self.tr, f))
                    for f, bf in enumerate(self.bfs)]
            self.fill_got = [np.asarray(f.result(), bool) for f in futs]

    def _warm_steps(self):
        return [(sec, j + 1, self.tr["rate"] * r)
                for j, (sec, r) in enumerate(self.tr["warmup"])]

    def warmup(self, sut: Sut) -> None:
        with self.phase("warmup"):
            for seconds, ph, rate in self._warm_steps():
                self.run_phase(seconds, ph, rate)

    def window(self, sut: Sut, seconds: float) -> None:
        self.seconds = seconds
        self.log = self.run_phase(seconds, 0, self.tr["rate"])
        sched = self.log["sched"]
        self.attempted = sum(len(s["due"]) for s in sched)
        for s in sched:
            kpr = s["keys"].shape[1]
            self.ops["add_keys"] += int(s["add"].sum()) * kpr
            self.ops["contains_keys"] += int((~s["add"]).sum()) * kpr

    def _latencies(self):
        lat, late = [], []
        for s, done, sent in zip(self.log["sched"], self.log["done"],
                                 self.log["sent"]):
            d = np.where(np.isnan(done), self.seconds + WAIT_AFTER_S, done)
            lat.append(d - s["due"])
            late.append(sent - s["due"])
        return np.concatenate(lat), np.concatenate(late)

    def e2e(self) -> dict:
        lat, _ = self._latencies()
        return {"p50_ms": 1e3 * float(np.quantile(lat, 0.5))}

    def tails(self) -> dict:
        """For the window line: the latency tail (no bound holds it on a
        shared host, see PERF.md) and how late the generator ran."""
        lat, late = self._latencies()
        late = late[~np.isnan(late)]
        return {"p99_ms": 1e3 * float(np.quantile(lat, 0.99)),
                "gen_late_p50_ms": 1e3 * float(np.quantile(late, 0.5)),
                "gen_late_p99_ms": 1e3 * float(np.quantile(late, 0.99)),
                "gen_late_max_ms": 1e3 * float(late.max())}

    def answers(self, k: int):
        """(reference answers with k probes, the program's answers) over
        every set-up add and every answered request of the window, key by
        key: one ordered stream of fill (checked), warm-up adds (not
        checked) and the window."""
        tr, seed = self.tr, self.seed
        nf = len(self.names)
        fids, ks, add, got, chk = [], [], [], [], []

        def part(f, keys, is_add, answers, checked):
            fids.append(f)
            ks.append(keys)
            add.append(is_add)
            got.append(answers)
            chk.append(checked)

        for f in range(nf):
            keys = gen.pool_fill(seed, tr, f)
            yes = np.ones(len(keys), bool)
            part(np.full(len(keys), f), keys, yes, self.fill_got[f], yes)
        for seconds, ph, rate in self._warm_steps():
            for p in range(tr["producers"]):
                s = gen.schedule(seed, {**tr, "rate": rate}, nf, seconds, p,
                                 ph)
                a = s["add"]
                keys = s["keys"][a].ravel()
                no = np.zeros(len(keys), bool)
                part(np.repeat(s["filt"][a], s["keys"].shape[1]), keys,
                     ~no, no, no)
        for s, res, answered in zip(self.log["sched"], self.log["got"],
                                    self.log["answered"]):
            kpr = s["keys"].shape[1]
            part(np.repeat(s["filt"], kpr), s["keys"].ravel(),
                 np.repeat(s["add"], kpr), res.ravel(),
                 np.repeat(answered, kpr))
        fids, ks, add, got, chk = map(np.concatenate,
                                      (fids, ks, add, got, chk))
        ref = reference.BloomReference(nf, self.m, k)
        return ref.expected(fids, ks, add, np.arange(len(ks)), chk), got[chk]


class OpenApi(OpenLoop):
    """Producer threads on the Python API (add_all_async /
    contains_all_async), each with a collector thread that waits for its
    replies in order."""

    def run_phase(self, seconds: float, ph: int, rate: float) -> dict:
        tr = {**self.tr, "rate": rate}
        P = tr["producers"]
        sched = [gen.schedule(self.seed, tr, len(self.names), seconds, p, ph)
                 for p in range(P)]
        n = [len(s["due"]) for s in sched]
        sent = [np.full(k, np.nan) for k in n]
        done = [np.full(k, np.nan) for k in n]
        got = [np.zeros((k, tr["keys_per_request"]), bool) for k in n]
        answered = [np.zeros(k, bool) for k in n]
        errors = []
        t0 = time.monotonic() + 0.2
        bfs = self.bfs

        def producer(p):
            s, q = sched[p], queues[p]
            due, filt, add, ks = s["due"], s["filt"], s["add"], s["keys"]
            for i in range(len(due)):
                d = t0 + due[i] - time.monotonic()
                if d > 0:
                    time.sleep(d)
                bf = bfs[filt[i]]
                with self.ann("bench:submit"):
                    try:
                        fut = (bf.add_all_async(ks[i]) if add[i]
                               else bf.contains_all_async(ks[i]))
                    except Exception as e:  # counted as failed, never fatal
                        errors.append(repr(e))
                        fut = None
                sent[p][i] = time.monotonic() - t0
                q.put((i, fut))
            q.put(None)

        def collector(p):
            q = queues[p]
            while True:
                item = q.get()
                if item is None:
                    return
                i, fut = item
                if fut is None:
                    continue
                try:
                    r = fut.result(timeout=max(
                        0.1, t0 + seconds + WAIT_AFTER_S - time.monotonic()))
                except Exception as e:
                    errors.append(repr(e))
                    continue
                done[p][i] = time.monotonic() - t0
                got[p][i] = np.asarray(r, bool)
                answered[p][i] = True

        queues = [queue.SimpleQueue() for _ in range(P)]
        threads = ([threading.Thread(target=producer, args=(p,))
                    for p in range(P)]
                   + [threading.Thread(target=collector, args=(p,))
                      for p in range(P)])
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if ph == 0:
            self.failed = sum(int((~a).sum()) for a in answered)
            self.errors = errors[:3]
        return {"sched": sched, "sent": sent, "done": done, "got": got,
                "answered": answered}


class OpenResp(OpenLoop):
    """RESP connections driven from load processes (``loadgen.py``);
    connection j owns filters f with f mod connections == j."""

    resp = True

    def setup(self, sut: Sut) -> None:
        import socket

        from benchmark import loadgen

        with self.phase("filters_init"):
            fp = repr(self.cfg["false_probability"]).encode()
            ei = str(self.cfg["expected_insertions"]).encode()
            cmds = b"".join(loadgen.wire(b"BF.RESERVE", n.encode(), fp, ei)
                            for n in self.names)
            want = b"+OK\r\n" * len(self.names)
            with socket.create_connection(("127.0.0.1",
                                           sut.server.port)) as s:
                s.sendall(cmds)
                buf = b""
                while len(buf) < len(want) and not buf.startswith(b"-"):
                    chunk = s.recv(1 << 16)
                    if not chunk:
                        break
                    buf += chunk
            if buf != want:
                raise RuntimeError(f"BF.RESERVE replies: {buf[:200]!r}")
        self.fill(sut)
        with self.phase("load_procs"):
            self._start_procs(sut.server.port)

    def _start_procs(self, port: int) -> None:
        from benchmark import loadgen

        ctx = multiprocessing.get_context("spawn")
        P, n = self.tr["producers"], self.tr["procs"]
        self.procs = []
        for w in range(n):
            here, there = ctx.Pipe()
            pr = ctx.Process(target=loadgen.main, daemon=True, args=(
                there, port, list(range(w, P, n)), self.seed, self.tr,
                self.names))
            pr.start()
            there.close()
            self.procs.append((pr, here))
        for _, c in self.procs:
            if c.recv() != "connected":
                raise RuntimeError("load process failed to connect")

    def run_phase(self, seconds: float, ph: int, rate: float) -> dict:
        for _, c in self.procs:
            c.send((seconds, ph, rate, WAIT_AFTER_S))
        for _, c in self.procs:
            if c.recv() != "ready":
                raise RuntimeError("load process not ready")
        t0 = time.monotonic() + 0.1
        for _, c in self.procs:
            c.send(t0)
        parts = [c.recv() for _, c in self.procs]
        tr = {**self.tr, "rate": rate}
        sched, sent, done, got, answered = [], [], [], [], []
        bad = None
        for p in range(tr["producers"]):
            part = parts[p % len(self.procs)]
            sched.append(gen.schedule(self.seed, tr, len(self.names), seconds,
                                      p, ph))
            v = part["val"][p]
            sent.append(part["sent"][p])
            done.append(np.where(v >= 0, part["done"][p], np.nan))
            got.append((v == 1)[:, None])
            answered.append(v >= 0)
            bad = bad or part["first_bad"]
        if ph == 0:
            self.failed = sum(int((~a).sum()) for a in answered)
            self.errors = [bad] if bad else []
        return {"sched": sched, "sent": sent, "done": done, "got": got,
                "answered": answered}

    def close(self) -> None:
        for pr, c in getattr(self, "procs", []):
            try:
                c.send(None)
            except OSError:
                pass
        for pr, c in getattr(self, "procs", []):
            pr.join(timeout=10)
            if pr.is_alive():
                pr.kill()
                pr.join()
            c.close()


DRIVERS = {"bulk": Bulk, "open_api": OpenApi, "open_resp": OpenResp}
