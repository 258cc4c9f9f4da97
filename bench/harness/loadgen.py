"""Load generator: a child process that drives POST /v1/generate (SSE).

It imports nothing but the standard library, so its threads never share
the server's interpreter lock, and never JAX, so it never touches the
chip.  Protocol over stdin/stdout, one line each:

  parent -> child   the plan (JSON, from traffic.plan) with "url"
  child  -> parent  "ready"
  parent -> child   "go"                 (time 0 of the schedule)
  child  -> parent  "open <t>"           (the measured window starts)
  child  -> parent  "closed <t>"         (the window ends)
  child  -> parent  "result <json>"      (every request's record)

Open loop: each request is sent at its due time whatever the server is
doing, and its latency counts from that due time.  Closed loop: a fixed
number of requests in flight; each is due when it is sent.  Every token
event is stamped as it is parsed.  After the window closes no request is
sent; the child waits until every request due in the window has its
first token (at most `first_token_wait_s`), then at most `drain_s` for
them to finish, and then closes what is still open.
"""
from __future__ import annotations

import json
import socket
import sys
import threading
import time
from http.client import HTTPConnection, HTTPException
from urllib.parse import urlsplit


class Load:
    def __init__(self, plan: dict):
        self.plan = plan
        self.reqs = plan["requests"]
        self.recs = {}
        self.lock = threading.Lock()
        self.conns = {}
        self.cutting = False
        self.ran_dry = False  # a closed loop used up its requests
        self.stop_sending = threading.Event()
        self.t0 = 0.0
        u = urlsplit(plan["url"])
        self.host, self.port = u.hostname, u.port

    def now(self) -> float:
        return time.monotonic() - self.t0

    # -- one request --------------------------------------------------------

    def one(self, req: dict, due: float):
        rec = {"id": req["id"], "due": due, "sent": self.now(),
               "max_new": req["max_new"], "prompt_len": len(req["tokens"]),
               "status": "open", "t": [], "tokens": [], "trace": None}
        with self.lock:
            self.recs[req["id"]] = rec
        body = json.dumps({"tokens": req["tokens"],
                           "max_new": req["max_new"],
                           "stream": True}).encode()
        conn = HTTPConnection(self.host, self.port, timeout=600)
        try:
            # the socket itself is kept for cutting: an SSE response
            # closes over it, and the connection then drops its own
            conn.connect()
            with self.lock:
                if self.cutting:
                    rec["status"] = "cut"
                    return
                self.conns[req["id"]] = conn.sock
            conn.request("POST", "/v1/generate", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            if resp.status != 200:
                rec["status"] = ("rejected" if resp.status == 429
                                 else f"http {resp.status}")
                rec["err"] = resp.read().decode(errors="replace")[:200]
                return
            buf = b""
            while True:
                chunk = resp.read1(65536)
                if not chunk:
                    rec["status"] = "cut" if self.cutting else "error"
                    rec["err"] = "stream closed before done"
                    return
                buf += chunk
                while b"\n\n" in buf:
                    block, buf = buf.split(b"\n\n", 1)
                    name, data = "message", []
                    for line in block.decode().splitlines():
                        if line.startswith("event:"):
                            name = line[6:].strip()
                        elif line.startswith("data:"):
                            data.append(line[5:].strip())
                    if not data:
                        continue
                    ev = json.loads("\n".join(data))
                    if name == "error":
                        rec["status"], rec["err"] = "error", ev.get("error")
                        return
                    if name == "done":
                        rec["trace"] = ev.get("trace")
                        rec["status"] = ("ok" if ev["tokens"] == rec["tokens"]
                                         else "error")
                        if rec["status"] == "error":
                            rec["err"] = "streamed tokens != final tokens"
                        return
                    rec["t"].append(self.now())
                    rec["tokens"].append(int(ev["token"]))
        except (OSError, ValueError, HTTPException) as e:
            rec["status"] = "cut" if self.cutting else "error"
            rec["err"] = repr(e)[:200]
        finally:
            with self.lock:
                self.conns.pop(req["id"], None)
            conn.close()

    # -- schedules -----------------------------------------------------------

    def open_loop(self, threads: list):
        for req in sorted(self.reqs, key=lambda r: r["due"]):
            wait = req["due"] - self.now()
            if wait > 0:
                time.sleep(wait)
            th = threading.Thread(target=self.one, args=(req, req["due"]),
                                  daemon=True)
            th.start()
            threads.append(th)

    def closed_loop(self, threads: list):
        it = iter(self.reqs)
        it_lock = threading.Lock()

        def worker():
            while not self.stop_sending.is_set():
                with it_lock:
                    req = next(it, None)
                if req is None:
                    self.ran_dry = True
                    return
                self.one(req, self.now())

        for _ in range(self.plan["concurrency"]):
            th = threading.Thread(target=worker, daemon=True)
            th.start()
            threads.append(th)

    def in_window_pending(self, end: float, need_first: bool) -> int:
        with self.lock:
            return sum(1 for r in self.recs.values()
                       if self.plan["ramp_s"] <= r["due"] < end
                       and r["status"] == "open"
                       and (not need_first or not r["t"]))

    def run(self):
        ramp, win = self.plan["ramp_s"], self.plan["window_s"]
        end = ramp + win
        threads: list = []
        self.t0 = time.monotonic()
        sender = threading.Thread(
            target=(self.open_loop if self.plan["mode"] == "open_loop"
                    else self.closed_loop), args=(threads,), daemon=True)
        sender.start()
        time.sleep(max(0.0, ramp - self.now()))
        print(f"open {self.now():.6f}", flush=True)
        time.sleep(max(0.0, end - self.now()))
        self.stop_sending.set()
        print(f"closed {self.now():.6f}", flush=True)
        sender.join()
        limit = end + self.plan["first_token_wait_s"]
        while self.now() < limit and self.in_window_pending(end, True):
            time.sleep(0.02)
        limit = self.now() + self.plan["drain_s"]
        while self.now() < limit and self.in_window_pending(end, False):
            time.sleep(0.02)
        with self.lock:
            self.cutting = True
            conns = list(self.conns.values())
        for sock in conns:
            try:
                sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        deadline = time.monotonic() + 30
        for th in threads:
            th.join(timeout=max(0.0, deadline - time.monotonic()))
        with self.lock:
            recs = [self.recs[k] for k in sorted(self.recs)]
        late = [r["sent"] - r["due"] for r in recs
                if self.plan["mode"] == "open_loop"]
        return {"window": [ramp, end], "records": recs,
                "ran_dry": self.ran_dry,
                "late_max_s": max(late, default=0.0),
                "late_mean_s": sum(late) / len(late) if late else 0.0}


def main() -> int:
    plan = json.loads(sys.stdin.readline())
    load = Load(plan)
    # one short request first, so the server's HTTP path and this
    # process's client path are warm before time 0
    load.t0 = time.monotonic()
    load.one({"id": -1, "tokens": list(range(1, 17)), "max_new": 4}, 0.0)
    rec = load.recs.pop(-1)
    if rec["status"] != "ok":
        print(f"warm-up request failed: {rec.get('err')}", file=sys.stderr,
              flush=True)
        return 3
    print("ready", flush=True)
    if sys.stdin.readline().strip() != "go":
        return 2
    out = load.run()
    print("result " + json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
