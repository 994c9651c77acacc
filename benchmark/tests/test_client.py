"""The client's failed-eval -> re-register path, against a stub server."""

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
    ``throttle`` jobs get one 429 first."""

    def __init__(self, fail_first=(), always_fail=(), throttle=()):
        self.fail_first, self.always_fail = set(fail_first), set(always_fail)
        self.throttle = set(throttle)
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
                body = json.loads(self.rfile.read(
                    int(self.headers.get("Content-Length", 0))) or b"{}")
                if self.path != "/v1/jobs":
                    return self._json(200, {})
                jid = body["Job"]["id"]
                num = int(jid.rsplit("-", 1)[1])
                if num in stub.throttle:
                    stub.throttle.discard(num)
                    return self._json(429, {"error": "slow down"},
                                      [("Retry-After", "0.05")])
                k = stub.registers[jid] = stub.registers.get(jid, 0) + 1
                stub.n += 1
                eid = f"eval-{stub.n}"
                bad = num in stub.always_fail or (
                    num in stub.fail_first and k == 1)
                stub.events.put({
                    "Topic": "Evaluation", "Index": stub.n, "Payload": {
                        "id": eid, "job_id": jid,
                        "status": "failed" if bad else "complete",
                        "queued_allocations": {}, "failed_tg_allocs": {},
                    }})
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
