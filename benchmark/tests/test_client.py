"""The client's failed-eval -> re-register path and its ``again`` operations
(an unchanged job registered again), against a stub server."""

import json
import queue
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import client as client_mod
import traffic


class Stub:
    """Registers jobs; the first eval of every job whose number is in
    ``fail_first`` ends failed, ``always_fail`` jobs never succeed, and
    ``throttle`` jobs (a number or an id) get one 429 first.  ``fail_at`` holds (job number,
    k): the job's k-th registration ends failed; ``stray_at`` likewise: a
    stray ``complete`` eval of the job, of no registration, is published
    first; ``slow_reply``: the register call returns only after its eval's
    event is out.  ``bodies`` keeps every PUT's bytes by job."""

    def __init__(self, fail_first=(), always_fail=(), throttle=()):
        self.fail_first, self.always_fail = set(fail_first), set(always_fail)
        self.throttle = set(throttle)
        self.fail_at, self.stray_at, self.slow_reply = set(), set(), set()
        self.bodies = {}
        self.registers = {}
        self.events = queue.Queue()
        self.n = 0
        stub = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def _json(self, code, body, headers=()):
                raw = json.dumps(body).encode()
                self.send_response(code)
                self.send_header("Content-Length", str(len(raw)))
                for k, v in headers:
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(raw)

            def do_PUT(self):
                raw = self.rfile.read(
                    int(self.headers.get("Content-Length", 0)))
                body = json.loads(raw or b"{}")
                if self.path != "/v1/jobs":
                    return self._json(200, {})
                jid = body["Job"]["id"]
                stub.bodies.setdefault(jid, []).append(raw)
                num = int(jid.rsplit("-", 1)[1])
                if {num, jid} & stub.throttle:
                    stub.throttle -= {num, jid}
                    return self._json(429, {"error": "slow down"},
                                      [("Retry-After", "0.05")])
                k = stub.registers[jid] = stub.registers.get(jid, 0) + 1
                stub.n += 1
                eid = f"eval-{stub.n}"
                bad = num in stub.always_fail or (
                    num in stub.fail_first and k == 1) or (
                    (num, k) in stub.fail_at)
                if (num, k) in stub.stray_at:
                    stub.events.put({
                        "Topic": "Evaluation", "Index": stub.n, "Payload": {
                            "id": f"stray-{stub.n}", "job_id": jid,
                            "status": "complete", "queued_allocations": {},
                            "failed_tg_allocs": {}}})
                stub.events.put({
                    "Topic": "Evaluation", "Index": stub.n, "Payload": {
                        "id": eid, "job_id": jid,
                        "status": "failed" if bad else "complete",
                        "queued_allocations": {}, "failed_tg_allocs": {},
                    }})
                if num in stub.slow_reply:
                    time.sleep(0.05)  # the event is out before the reply
                self._json(200, {"EvalID": eid})

            def do_GET(self):
                self.send_response(200)
                self.send_header("Connection", "close")
                self.end_headers()
                while True:
                    ev = stub.events.get()
                    if ev is None:
                        return
                    time.sleep(0.002)  # after the register call returned
                    self.wfile.write((json.dumps(ev) + "\n").encode())
                    self.wfile.flush()

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()
        self.addr = f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def close(self):
        self.events.put(None)
        self.httpd.shutdown()


def _run(stub, name="steady", seconds=1.0):
    t = dict(traffic.load(name), limit_s=2.0, max_reregister=5)
    c = client_mod.Client(stub.addr, t, seed=5, seconds=seconds)
    c.start()
    try:
        return c.run(time.time() + 0.1, seconds)
    finally:
        c.stop = True
        stub.close()


def test_failed_eval_is_registered_again_and_the_operation_placed():
    stub = Stub(fail_first={3, 10}, throttle={5})
    out = _run(stub)
    recs = {r["i"]: r for r in out["records"]}
    assert len(recs) == 61
    assert all(r["status"] == "placed" for r in recs.values())
    assert recs[3]["registers"] == 2 and recs[3]["evals_failed"] == 1
    assert recs[10]["registers"] == 2
    assert recs[0]["registers"] == 1 and recs[0]["evals_failed"] == 0
    assert recs[5]["n429"] == 1 and recs[5]["registers"] == 1
    assert out["evals_failed"] == 2
    assert out["evals_ended"] == 63
    # The clock ran through the re-registration.
    assert recs[3]["placed"] > recs[3]["sent"] > recs[3]["due"] - 1e-3


def test_an_eval_that_never_succeeds_fails_the_operation_after_5_more_tries():
    stub = Stub(always_fail={7})
    out = _run(stub)
    r = {r["i"]: r for r in out["records"]}[7]
    assert r["status"] == "failed" and r["cause"] == "eval_out_of_attempts"
    assert r["registers"] == 6 and r["evals_failed"] == 6


def test_closed_loop_keeps_its_operations_outstanding():
    stub = Stub(fail_first={2})
    out = _run(stub, "backlog", seconds=0.5)
    assert out["loop"] == "closed"
    assert len(out["records"]) > 50
    assert all(r["status"] == "placed" for r in out["records"])
    assert all(r["due"] <= out["t_end"] for r in out["records"])


# -- ``again`` operations: a resident job registered again, unchanged ---------------

def _resubmit(prepare=lambda stub: None, seconds=0.4):
    """The resident phase, then a window of half new jobs and half resident
    jobs registered again; ``prepare`` arms the stub between the two."""
    stub = Stub()
    t = dict(traffic.load("backlog"), limit_s=2.0, max_reregister=5,
             outstanding=4, resident_jobs=16, register_again_fraction=0.5)
    c = client_mod.Client(stub.addr, t, seed=2 ** 31 + 43, seconds=seconds)
    c.start()
    try:
        resident = c.resident()
        assert resident["placed"] == resident["ops"] == 16
        assert resident["jobs"] == [
            f"res-{i:06d}" for i in range(16)]
        prepare(stub)
        out = c.run(time.time() + 0.1, seconds)
    finally:
        c.stop = True
        stub.close()
    return stub, out


def test_an_again_operation_sends_the_resident_payload_unchanged():
    stub, out = _resubmit()
    again = [r for r in out["records"] if r["kind"] == "again"]
    new = [r for r in out["records"] if r["kind"] == "new"]
    assert len(again) > 20 and len(new) > 20
    assert all(r["status"] == "placed" for r in out["records"])
    assert all(r["job_id"].startswith("res-") and r["registers"] == 1
               for r in again)
    assert all(r["job_id"].startswith("op-") for r in new)
    for jid, bodies in stub.bodies.items():
        if jid.startswith("res-"):
            assert len(set(bodies)) == 1, jid  # byte for byte as first sent
    sent_again = sum(len(b) - 1 for j, b in stub.bodies.items()
                     if j.startswith("res-"))
    assert sent_again == len(again) > 16  # some resident jobs twice or more
    assert out["evals_ended"] == len(out["records"])
    # Begun -> placed on the operation's own clock, not its resident's.
    assert all(r["placed"] >= r["sent"] >= r["due"] >= out["t0"]
               for r in again)


def test_an_again_operation_ends_on_its_own_evals_complete():
    """A stray ``complete`` eval of the same job ends nothing: the
    operation's own eval fails, the job is registered again, and that
    registration's eval, though its event is out before the register call
    returns, places it."""
    def prepare(stub):
        stub.stray_at = {(n, 2) for n in range(16)}
        stub.fail_at = {(n, 2) for n in range(16)}
        stub.slow_reply = {0, 1, 2, 3}

    stub, out = _resubmit(prepare)
    again = [r for r in out["records"] if r["kind"] == "again"]
    first = {}
    for r in again:
        first.setdefault(r["job_id"], r)
    assert len(first) == 16
    for r in again:
        assert r["status"] == "placed"
        if r is first[r["job_id"]]:   # the job's 2nd registration failed
            assert (r["registers"], r["evals_failed"]) == (2, 1), r
        else:
            assert (r["registers"], r["evals_failed"]) == (1, 0), r
    assert out["evals_failed"] == 16


def test_an_again_operation_is_retried_on_a_429():
    def prepare(stub):
        stub.throttle = {"res-000005", "res-000006"}

    stub, out = _resubmit(prepare)
    again = [r for r in out["records"] if r["kind"] == "again"]
    hit = [r for r in again if r["n429"]]
    assert {r["job_id"] for r in hit} == {"res-000005", "res-000006"}
    assert all(r["n429"] == 1 and r["registers"] == 1
               and r["status"] == "placed" for r in hit)
    assert all(r["status"] == "placed" for r in out["records"])
