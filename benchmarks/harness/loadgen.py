"""The load generator: a JAX-free process (stdlib only) that drives the server
over loopback HTTP with ``"stream": true`` and stamps every token event with
its own clock.  ``python loadgen.py --base URL --mix FILE --seed N --vocab V
--seconds S --out FILE``; one JSON line per request goes to ``--out``, the
first line says when the measured window starts and ends.

Every request is timed from when it was *due* (closed loop: when the client's
previous reply ended; open loop: its arrival time), and ``sent - due`` is
reported as the generator's own lateness.  At the window's end the sockets of
requests still streaming are shut: those requests are recorded as ``cut`` with
the tokens they had, they are not failures.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import socket
import sys
import threading
import time
from urllib.parse import urlparse

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import tokens, traffic  # noqa: E402

PATHS = {"completions": "/v1/completions", "chat": "/v1/chat/completions"}


class Sockets:
    """The sockets of requests in flight, so the window's end can shut them."""

    def __init__(self):
        self._lock = threading.Lock()
        self._live: set = set()
        self.closed = False

    def add(self, s) -> bool:
        with self._lock:
            if self.closed:
                return False
            self._live.add(s)
            return True

    def drop(self, s) -> None:
        with self._lock:
            self._live.discard(s)

    def shut_all(self) -> None:
        with self._lock:
            self.closed = True
            for s in self._live:
                try:
                    s.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass


def send_request(base: str, endpoint: str, ids: list[int], max_tokens: int, *,
                 due: float | None = None, stream: bool = True,
                 socks: Sockets | None = None, timeout: float = 300.0,
                 keep_text: bool = False) -> dict:
    """One greedy request.  Returns its record: ``due``, ``sent``, ``times``
    (one stamp per token event), ``n_out``, ``ok``, ``cut``, ``status``,
    ``finish`` and, if asked, ``text`` (non-streaming: also ``usage``)."""
    text = tokens.text_of(ids)
    body = {"max_tokens": max_tokens, "temperature": 0, "stream": stream}
    if endpoint == "chat":
        body["messages"] = [{"role": "user", "content": text}]
    else:
        body["prompt"] = text
    u = urlparse(base)
    rec = {"due": due, "n_prompt": len(ids) + tokens.overhead(endpoint),
           "max_tokens": max_tokens, "times": [], "ok": False, "cut": False,
           "status": 0, "finish": None}
    parts: list[str] = []
    conn = http.client.HTTPConnection(u.hostname, u.port, timeout=timeout)
    try:
        conn.connect()
        if socks is not None and not socks.add(conn.sock):
            rec["cut"] = True
            return rec
        rec["sent"] = time.time()
        if due is None:
            rec["due"] = rec["sent"]
        conn.request("POST", PATHS[endpoint], json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read(400).decode("utf-8", "replace")
            return rec
        if not stream:
            out = json.loads(resp.read())
            rec["usage"] = out.get("usage")
            ch = out["choices"][0]
            parts.append(ch["text"] if endpoint == "completions"
                         else ch["message"]["content"])
            rec["finish"], rec["ok"] = ch.get("finish_reason"), True
            return rec
        while True:
            line = resp.fp.readline()
            if not line:
                break
            now = time.time()
            if not line.startswith(b"data: "):
                continue
            if line.startswith(b"data: [DONE]"):
                rec["ok"] = "error" not in rec
                break
            ev = json.loads(line[6:])
            if "error" in ev:
                rec["error"] = str(ev["error"])[:400]
                continue
            ch = ev["choices"][0]
            piece = ch.get("text") if endpoint == "completions" \
                else (ch.get("delta") or {}).get("content")
            if piece:  # one event with text per generated token
                rec["times"].append(now)
                if keep_text:
                    parts.append(piece)
            if ch.get("finish_reason"):
                rec["finish"] = ch["finish_reason"]
    except (OSError, http.client.HTTPException, ValueError) as e:
        if socks is not None and socks.closed:
            rec["cut"] = True
        else:
            rec["error"] = repr(e)[:400]
    finally:
        if socks is not None and conn.sock is not None:
            socks.drop(conn.sock)
        conn.close()
        rec["end"] = time.time()
        if not rec["ok"] and "error" not in rec and rec["status"] in (0, 200) \
                and socks is not None and socks.closed:
            rec["cut"] = True  # the window's end shut the socket mid-stream
        rec["n_out"] = len(rec["times"])
        if keep_text or not stream:
            rec["text"] = "".join(parts)
    return rec


def run_window(base: str, mix: dict, seed: int, vocab: int, seconds: float,
               out_path: str) -> None:
    endpoint = mix["endpoint"]
    over = tokens.overhead(endpoint)
    socks = Sockets()
    lock = threading.Lock()
    next_job = [0]
    t_launch = time.time()
    t0 = t_launch + float(mix.get("preroll_s", 0))
    t1 = t0 + seconds
    think = float((mix.get("session") or {}).get("think_s", 0))
    out = open(out_path, "w")
    out.write(json.dumps({"window": [t0, t1], "launch": t_launch}) + "\n")

    def take_job() -> int:
        with lock:
            j = next_job[0]
            next_job[0] += 1
            return j

    def run_job(j: int, due: float) -> None:
        for k, turn in enumerate(traffic.job(mix, seed, j, vocab, over)):
            if time.time() >= t1 or socks.closed:
                return
            rec = send_request(base, endpoint, turn["ids"], turn["max_tokens"],
                               due=due, socks=socks)
            rec.update(job=j, turn=k)
            with lock:
                out.write(json.dumps(rec) + "\n")
            due = rec["end"] + think

    def closed_client() -> None:
        due = time.time()
        while time.time() < t1 and not socks.closed:
            run_job(take_job(), due)
            due = time.time()

    threads = []
    if mix["loop"] == "closed":
        threads = [threading.Thread(target=closed_client, daemon=True)
                   for _ in range(int(mix["clients"]))]
        for th in threads:
            th.start()
    elif mix["loop"] == "open":
        cap = threading.Semaphore(int(mix.get("max_inflight", 256)))

        def open_job(j: int, due: float) -> None:
            try:
                run_job(j, due)
            finally:
                cap.release()

        for rel in traffic.arrivals(mix, seed, t1 - t_launch):
            due = t_launch + rel
            time.sleep(max(due - time.time(), 0))
            cap.acquire()  # a full pool shows as lateness, not as lost load
            th = threading.Thread(target=open_job, args=(take_job(), due),
                                  daemon=True)
            th.start()
            threads.append(th)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    time.sleep(max(t1 - time.time(), 0))
    socks.shut_all()
    for th in threads:
        th.join(timeout=30)
    with lock:
        out.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--base", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--vocab", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    with open(a.mix) as f:
        mix = json.load(f)
    run_window(a.base, mix, a.seed, a.vocab, a.seconds, a.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
