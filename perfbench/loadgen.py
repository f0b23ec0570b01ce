"""Load from one process with few threads, against ``POST /generate``.  It
runs in the harness's parent, which never imports JAX, so the interpreter
that drives the engine is not the one that paces the requests.  Rates are
fixed by the traffic file; nothing here searches for one.  No JAX.
"""

from __future__ import annotations

import http.client
import json
import threading
import time

from perfbench import traffic as traffic_lib

CLOCK = time.monotonic


def _post(port: int, body: bytes, timeout: float):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", "/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


def _send(port, item, seed, vocab, timeout, rec):
    """One request; ``rec`` is filled in place.  Greedy (temperature 0), so
    the ``seed`` field of the wire format is free to carry the request's
    index, by which the engine-side log finds it again."""
    prompt = traffic_lib.prompt_tokens(seed, item["index"],
                                       item["prompt_len"], vocab)
    body = json.dumps({"prompt": prompt, "num_tokens": item["num_tokens"],
                       "seed": item["index"]}).encode()
    rec.update(index=item["index"], prompt_len=item["prompt_len"],
               num_tokens=item["num_tokens"], ok=False, status=None,
               served=None, prompt=prompt)
    rec["sent"] = CLOCK()
    try:
        status, payload = _post(port, body, timeout)
        rec["status"] = status
        if status == 200:
            served = payload["tokens"][len(prompt):]
            rec["served"], rec["ok"] = served, len(served) > 0
            rec["server"] = {k: payload.get(k) for k in
                             ("queue_ms", "ttft_ms", "tpot_ms")}
    except (OSError, ValueError, KeyError) as e:
        rec["status"] = f"{type(e).__name__}: {e}"
    rec["done"] = CLOCK()


def open_loop(port: int, tr: dict, seed: int, vocab: int, t0: float,
              seconds: float, extra_s: float) -> list[dict]:
    """Send every request of the paced schedule at its due time, whatever
    has come back.  The same comb goes on after the window (``tail``
    requests, not measured) until every request due in the window has come
    back and ``extra_s`` has passed, so the measured requests finish under
    the load they started under; then return the records that are
    complete."""
    drain = float(tr.get("drain_s", 0.0))
    items = traffic_lib.open_schedule(tr, seconds, seed, extra_s + drain)
    records, measured = [], []
    timeout = tr["request_timeout_s"] + 30.0
    for item in items:
        due = t0 + item["due"]
        if item["phase"] == "tail" and due >= t0 + seconds + extra_s \
                and not any(th.is_alive() for th in measured):
            break
        delay = due - CLOCK()
        if delay > 0:
            time.sleep(delay)
        rec = {"due": due, "phase": item["phase"], "done": None}
        records.append(rec)
        th = threading.Thread(target=_send, daemon=True,
                              args=(port, item, seed, vocab, timeout, rec))
        th.start()
        if item["phase"] == "window":
            measured.append(th)
    for th in measured:
        th.join()
    return [dict(r) for r in records if r.get("done") is not None]


def closed_loop(port: int, tr: dict, seed: int, vocab: int, t0: float,
                seconds: float, extra_s: float) -> list[dict]:
    """``callers`` callers, each sending its next request on the reply, from
    ``lead_s`` before the window to its end (and ``extra_s`` beyond in a
    traced run).  A request is "due" when it is sent.  Returns the requests
    that had come back by then."""
    seq = traffic_lib.closed_sequence(tr, seed)
    lock = threading.Lock()
    records: list[dict] = []
    stop_at = t0 + seconds + extra_s
    timeout = tr["request_timeout_s"] + 30.0

    def caller():
        while CLOCK() < stop_at:
            with lock:
                item = next(seq)
                now = CLOCK()
                rec = {"due": now, "done": None,
                       "phase": ("lead" if now < t0 else
                                 "window" if now < t0 + seconds else "tail")}
                records.append(rec)
            _send(port, item, seed, vocab, timeout, rec)

    delay = t0 - float(tr.get("lead_s", 0.0)) - CLOCK()
    if delay > 0:
        time.sleep(delay)
    for _ in range(int(tr["callers"])):
        threading.Thread(target=caller, daemon=True).start()
    time.sleep(max(0.0, stop_at - CLOCK()))
    # No drain: the cell's rate is counted on the engine's side over whole
    # steps, so the requests still in flight are left to end with the
    # server.  They are neither attempted nor failed.
    with lock:
        return [dict(r) for r in records if r.get("done") is not None]


def drive(port, tr, seed, vocab, t0, seconds, extra_s):
    run = open_loop if tr["loop"] == "open_paced" else closed_loop
    return run(port, tr, seed, vocab, t0, seconds, extra_s)
