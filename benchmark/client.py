"""The benchmark's client and load generator, in a process of its own.

``run.py`` starts this before it imports JAX.  It never imports JAX or any
part of ``nomad_tpu``: it is a user of the HTTP API and nothing else, so
the generator does not share a GIL with 16 scheduler workers and 10,000
heartbeats.  It speaks JSON lines with its parent over stdin / stdout:

    {"cmd": "init", "addr", "traffic", "seed", "seconds", "overrides"}
    {"cmd": "warmup", "tag"}         one op of every shape class, then a
                                     burst of the window's own first ops
    {"cmd": "resident"}              register and place the mix's resident
                                     jobs (where it has any), to the end
    {"cmd": "run", "t0", "seconds"}  the measured window, then the drain
    {"cmd": "exit"}

One operation is one job registration that ends with the job placed: the
client registers the job, and learns from its ONE ``/v1/event/stream``
subscription that an eval of that job reached ``complete`` with nothing
queued.  An eval that ends ``failed`` (it lost the plan race too often)
makes the client register the same job again, as ``nomad job run`` in a
pipeline would, until it is placed or ``limit_s`` has passed (and at most
``max_reregister`` times); a 429 is retried after its ``Retry-After``.  The
operation's clock runs through all of it.

An operation's ``kind`` is data (traffic.py): ``new`` registers a new job,
``again`` registers a resident job once more with the bytes it was first
sent.  Several operations name one resident job over a run, so an ``again``
operation ends on the eval its OWN registration returned, and on no other
eval of that job.
"""

from __future__ import annotations

import collections
import http.client
import json
import os
import sys
import threading
import time
import urllib.parse

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import traffic as traffic_mod  # noqa: E402

SENDERS = 8
MAX_429 = 10
WARMUP_TIMEOUT_S = 900.0


class Op:
    __slots__ = (
        "i", "job_id", "spec", "due", "sent", "sent_last", "placed",
        "registers", "evals_failed", "n429", "blocked", "status", "cause",
        "evals", "submit_s", "gen", "kind",
    )

    def __init__(self, spec, job_id):
        self.i = spec["i"]
        self.job_id = job_id
        self.spec = spec
        self.kind = spec.get("kind", "new")
        self.due = None        # wall time the registration was due / begun
        self.sent = None       # wall time the first register call started
        self.sent_last = None
        self.placed = None     # wall time the client learned it was placed
        self.registers = 0
        self.evals_failed = 0
        self.n429 = 0
        self.blocked = 0
        self.status = "open"   # open | placed | failed
        self.cause = ""
        self.evals = []        # eval ids, in order of registration
        self.submit_s = None
        self.gen = 0

    def record(self):
        return {
            "i": self.i, "job_id": self.job_id,
            "namespace": self.spec["namespace"], "width": self.spec["width"],
            "type": self.spec["type"], "shape": self.spec["shape"],
            "kind": self.kind, "due": self.due, "sent": self.sent,
            "placed": self.placed,
            "registers": self.registers, "evals_failed": self.evals_failed,
            "n429": self.n429, "blocked": self.blocked,
            "status": self.status, "cause": self.cause,
            "submit_s": self.submit_s,
        }


class Client:
    def __init__(self, addr, traffic, seed, seconds):
        u = urllib.parse.urlparse(addr)
        self.host, self.port = u.hostname, u.port
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.lock = threading.Condition()
        self.ops = {}            # job_id -> Op (every phase)
        self.seen_evals = set()  # terminal eval ids already handled
        self.retries = collections.deque()  # ops to register again
        self.bodies = {}         # resident job id -> the bytes first sent
        self.own = {}            # eval id -> the ``again`` op it belongs to
        self.early = {}          # eval id -> (payload, time): an ``again``
        #                          op's eval that ended before its id was known
        self.evals_ended = 0
        self.evals_failed = 0
        self.eval_failed_causes = {}
        self.stream_gaps = 0
        self.stream_reconnects = 0
        self.stream_bytes = 0
        self.last_index = 0
        self.stop = False
        self._tls = threading.local()
        self.phase = None
        self.gen = 0
        self.ended = 0           # ops of the current phase that ended
        self._stream_ready = threading.Event()
        self.stream_thread = threading.Thread(
            target=self._stream_loop, name="client-stream", daemon=True
        )

    def _end(self, op, status, cause=""):
        """Close an open op (caller holds the lock)."""
        if op.status != "open":
            return
        op.status, op.cause = status, cause
        if op.gen == self.gen:
            self.ended += 1
        self.lock.notify_all()

    # -- HTTP -------------------------------------------------------------

    def _conn(self):
        c = getattr(self._tls, "conn", None)
        if c is None:
            c = http.client.HTTPConnection(self.host, self.port, timeout=30)
            self._tls.conn = c
        return c

    def call(self, method, path, body=None, data=None):
        """(status, headers, parsed body); one reconnect on a dropped
        keep-alive connection.  ``data``: the body's bytes as they are."""
        if body is not None:
            data = json.dumps(body).encode()
        for attempt in (0, 1):
            c = self._conn()
            try:
                c.request(method, path, body=data,
                          headers={"Content-Type": "application/json"})
                r = c.getresponse()
                raw = r.read()
                try:
                    parsed = json.loads(raw or b"null")
                except ValueError:
                    parsed = {"error": raw[:200].decode("latin-1")}
                return r.status, r.headers, parsed
            except (OSError, http.client.HTTPException):
                c.close()
                self._tls.conn = None
                if attempt:
                    raise

    # -- the one event-stream subscription ----------------------------------

    def _stream_loop(self):
        while not self.stop:
            conn = http.client.HTTPConnection(self.host, self.port,
                                              timeout=60)
            try:
                path = "/v1/event/stream?topic=Evaluation:*"
                if self.last_index:
                    path += f"&index={self.last_index}"
                conn.request("GET", path)
                resp = conn.getresponse()
                self._stream_ready.set()
                while not self.stop:
                    line = resp.fp.readline()
                    if not line:
                        break
                    self.stream_bytes += len(line)
                    if len(line) > 3:
                        self._on_event(json.loads(line))
            except (OSError, ValueError, http.client.HTTPException):
                pass
            finally:
                conn.close()
            if not self.stop:
                self.stream_reconnects += 1
                time.sleep(0.05)

    def _on_event(self, ev):
        now = time.time()
        if ev.get("Topic") == "Framework":
            # The ring dropped events we asked for: read the outstanding
            # evals once and go on.
            self.stream_gaps += 1
            threading.Thread(target=self._poll_outstanding,
                             daemon=True).start()
            return
        self.last_index = max(self.last_index, int(ev.get("Index") or 0))
        p = ev.get("Payload") or {}
        if ev.get("Topic") == "Evaluation":
            self._on_eval(p, now)

    def _on_eval(self, p, now):
        status = p.get("status")
        if status not in ("complete", "failed", "canceled"):
            return
        with self.lock:
            if p["id"] in self.seen_evals:
                return
            op = self.own.pop(p["id"], None) or self.ops.get(p.get("job_id"))
            if (op is not None and op.kind == "again"
                    and p["id"] not in op.evals):
                # An eval of this job that is not (or not yet known to be)
                # an ``again`` registration's own: kept for _register to
                # claim.
                self.early[p["id"]] = (p, now)
                return
            self.seen_evals.add(p["id"])
            if op is None:
                return
            if op.due is not None and self.phase == "run":
                self.evals_ended += 1
            if status == "complete":
                queued = sum((p.get("queued_allocations") or {}).values())
                if queued or p.get("failed_tg_allocs"):
                    op.blocked += 1  # stays open: a blocked eval follows
                elif op.status == "open":
                    op.placed = now
                    self._end(op, "placed")
            elif status == "failed" and op.status == "open":
                op.evals_failed += 1
                if self.phase == "run":
                    self.evals_failed += 1
                    why = p.get("status_description") or "?"
                    self.eval_failed_causes[why] = (
                        self.eval_failed_causes.get(why, 0) + 1)
                if op.registers <= self.traffic["max_reregister"]:
                    self.retries.append(op)
                    self.lock.notify_all()
                else:
                    self._end(op, "failed", "eval_out_of_attempts")

    def _poll_outstanding(self):
        with self.lock:
            todo = [(op, op.evals[-1]) for op in self.ops.values()
                    if op.status == "open" and op.evals]
        for op, eid in todo:
            try:
                code, _, ev = self.call("GET", f"/v1/evaluation/{eid}")
            except (OSError, http.client.HTTPException):
                continue
            if code == 200 and isinstance(ev, dict):
                self._on_eval(ev, time.time())

    # -- registering ----------------------------------------------------------

    def _register(self, op, prefix):
        # A new job's body is serialised inside the timed call, as it always
        # was (``submit_s`` counts it); an ``again`` operation sends the
        # bytes the resident phase sent.
        payload = data = None
        if op.kind == "again":
            data = self.bodies[op.job_id]
        else:
            payload = {"Job": traffic_mod.job_payload(self.traffic, op.spec,
                                                       prefix)}
            if self.phase == "resident":
                data = self.bodies[op.job_id] = json.dumps(payload).encode()
                payload = None
        for _ in range(MAX_429 + 1):
            t = time.time()
            with self.lock:
                if op.sent is None:
                    op.sent = t
                op.sent_last = t
                op.registers += 1
            try:
                code, headers, body = self.call("PUT", "/v1/jobs", payload,
                                                data)
            except (OSError, http.client.HTTPException) as e:
                code, headers, body = 599, {}, {"error": repr(e)}
            took = time.time() - t
            if code == 200 and body.get("EvalID"):
                with self.lock:
                    op.evals.append(body["EvalID"])
                    if op.submit_s is None:
                        op.submit_s = took
                    if op.kind == "again":
                        self.own[body["EvalID"]] = op
                    early = self.early.pop(body["EvalID"], None)
                if early is not None:
                    self._on_eval(*early)
                return
            with self.lock:
                op.registers -= 1  # a refused call registered nothing
            if code == 429:
                op.n429 += 1
                try:
                    wait = float(headers.get("Retry-After") or 0.5)
                except ValueError:
                    wait = 0.5
                time.sleep(min(max(wait, 0.05), 5.0))
                continue
            with self.lock:
                self._end(op, "failed", f"refused_{code}")
            return
        with self.lock:
            self._end(op, "failed", "shed_429")

    # -- one phase: send a list of ops, open or closed loop ---------------------

    def drive(self, specs, prefix, loop, t0, seconds, outstanding, limit_s,
              phase):
        """Send ``specs``.  ``loop`` "open": each at t0 + spec.due.
        "closed": keep ``outstanding`` in flight, begin none after
        t0 + seconds.  "all": as closed, until every op has ended.
        Returns the ops in order."""
        ops = [Op(s, s["job_id"] if s.get("kind") == "again"
                  else prefix + s["job_id"]) for s in specs]
        with self.lock:
            self.phase = phase
            self.gen += 1
            self.ended = 0
            for op in ops:
                op.gen = self.gen
                if op.kind != "again":  # those take the job as they begin
                    self.ops[op.job_id] = op
        t_end = t0 + seconds if seconds is not None else float("inf")
        state = {"next": 0}
        if loop == "open":
            state["deadline"] = t_end + limit_s
        elif loop == "all":
            state["deadline"] = time.time() + limit_s

        def next_item():
            with self.lock:
                while True:
                    now = time.time()
                    if self.retries:
                        return self.retries.popleft()
                    if state["next"] >= len(ops) or state.get("closed"):
                        if self.ended >= state["next"]:
                            return None
                        if now >= state.get("deadline", float("inf")):
                            return None
                        self.lock.wait(0.05)
                        continue
                    op = ops[state["next"]]
                    if loop == "open":
                        due = t0 + op.spec["due"]
                        if due <= now:
                            op.due = due
                            state["next"] += 1
                            self.ops[op.job_id] = op
                            return op
                        self.lock.wait(min(due - now, 0.05))
                        continue
                    if now >= t_end:
                        state["closed"] = True
                        state["deadline"] = now + limit_s
                        continue
                    if now < t0:
                        self.lock.wait(min(t0 - now, 0.05))
                        continue
                    if state["next"] - self.ended < outstanding:
                        op.due = now
                        state["next"] += 1
                        self.ops[op.job_id] = op
                        return op
                    self.lock.wait(0.05)

        def sender():
            while True:
                op = next_item()
                if op is None:
                    return
                self._register(op, prefix)

        threads = [threading.Thread(target=sender, daemon=True)
                   for _ in range(SENDERS)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        with self.lock:
            self.retries.clear()
            self.early.clear()
            self.own.clear()
            self.phase = None
        return ops[: state["next"]], t_end

    # -- commands ----------------------------------------------------------------

    def start(self):
        for ns in traffic_mod.namespaces(self.traffic):
            if ns != "default":
                self.call("PUT", f"/v1/namespace/{ns}",
                          {"Description": "benchmark tenant"})
        self.stream_thread.start()
        self._stream_ready.wait(30)

    def warmup(self, tag):
        t = self.traffic
        specs = traffic_mod.warmup_ops(t)
        ops, _ = self.drive(specs, f"{tag}s-", "all", time.time(), None,
                            4, WARMUP_TIMEOUT_S, "warmup")
        # Then the window's own concurrency: its first operations at once.
        n = int(min(t.get("outstanding", 64), 64))  # 16 workers, 64 lanes
        burst = [s for s in traffic_mod.schedule(t, self.seed, self.seconds)
                 if s.get("kind") != "again"][:n]
        ops2, _ = self.drive(burst, f"{tag}b-", "all", time.time(), None,
                             n, WARMUP_TIMEOUT_S, "warmup")
        done = ops + ops2
        return {
            "ops": len(done),
            "placed": sum(op.status == "placed" for op in done),
            "not_placed": [op.job_id + ":" + (op.cause or op.status)
                           for op in done if op.status != "placed"][:8],
        }

    def resident(self):
        """Register and place the resident set, closed loop to the end;
        the bytes of each registration are kept for the window's ``again``
        operations."""
        t = self.traffic
        t_begin = time.time()
        ops, _ = self.drive(
            traffic_mod.resident_ops(t, self.seed), "", "all", t_begin, None,
            int(t.get("outstanding", 64)), WARMUP_TIMEOUT_S, "resident")
        return {
            "ops": len(ops), "seconds": time.time() - t_begin,
            "placed": sum(op.status == "placed" for op in ops),
            "not_placed": [op.job_id + ":" + (op.cause or op.status)
                           for op in ops if op.status != "placed"][:8],
            "jobs": [op.job_id for op in ops],
        }

    def run(self, t0, seconds):
        t = self.traffic
        specs = traffic_mod.schedule(t, self.seed, seconds)
        self.stream_bytes = 0
        ops, t_end = self.drive(
            specs, "", t["loop"], t0, seconds, t.get("outstanding", 0),
            t["limit_s"], "run",
        )
        for op in ops:
            if op.status == "open":
                op.cause = "blocked" if op.blocked else "not_placed_in_limit"
        # The longest stretch of the window in which no register call
        # returned: a stall of the server (or of this process).
        sends = sorted(op.sent_last for op in ops if op.sent_last)
        stall, stall_at = max(
            ((b - a, a - t0) for a, b in zip(sends, sends[1:])
             if a >= t0 and b <= t_end), default=(0.0, 0.0))
        return {
            "t0": t0, "t_end": t_end, "loop": t["loop"],
            "stall_ms": stall * 1e3, "stall_at_s": stall_at,
            "limit_s": t["limit_s"], "scheduled": len(specs),
            "records": [op.record() for op in ops],
            "evals_ended": self.evals_ended,
            "evals_failed": self.evals_failed,
            "eval_failed_causes": self.eval_failed_causes,
            "stream_gaps": self.stream_gaps,
            "stream_reconnects": self.stream_reconnects,
            "stream_bytes": self.stream_bytes,
        }


def main():
    out = sys.stdout
    sys.stdout = sys.stderr  # nothing but protocol lines on the pipe
    client = None
    for line in sys.stdin:
        msg = json.loads(line)
        cmd = msg["cmd"]
        try:
            if cmd == "init":
                mix = traffic_mod.load(msg["traffic"])
                mix.update(msg.get("overrides") or {})
                client = Client(msg["addr"], mix, msg["seed"], msg["seconds"])
                client.start()
                reply = {"ok": True}
            elif cmd == "warmup":
                reply = client.warmup(msg["tag"])
            elif cmd == "resident":
                reply = client.resident()
            elif cmd == "run":
                reply = client.run(msg["t0"], msg["seconds"])
            elif cmd == "exit":
                break
            else:
                reply = {"error": f"unknown command {cmd}"}
        except Exception as e:  # noqa: BLE001 — the parent decides
            import traceback

            traceback.print_exc()
            reply = {"error": f"{type(e).__name__}: {e}"}
        out.write(json.dumps(reply) + "\n")
        out.flush()
    if client is not None:
        client.stop = True


if __name__ == "__main__":
    main()
