"""The load generator: a process of its own that never imports JAX (the chip
belongs to the parent, and so does the parent's interpreter lock).

    python3 -m benchmark.loadgen <plan.json> <results.json>

Protocol on the standard streams: it sends the plan's warm-up stages, prints
``READY``, waits for a line on stdin (``GO``), takes t0 on the system-wide
monotonic clock, runs the measured phase, writes the results and prints
``DONE``. Every time it records is seconds after t0.

It differs from ``kubeflow_tpu/loadgen/runner.py::ServerTarget`` where that
one is unfit for a yardstick: a request is timed from the instant it was DUE,
every streamed token is stamped, and prompts are token ids over the whole
vocabulary.
"""

from __future__ import annotations

import http.client
import json
import socket
import sys
import threading
import time
from urllib.parse import urlparse

from benchmark.traffic import decode_ids, encode_ids, prompt_tokens


def _body(plan: dict, req: dict) -> bytes:
    return json.dumps({
        "model": plan["model"], "prompt": encode_ids(prompt_tokens(plan, req)),
        "max_tokens": req["max_tokens"], "temperature": plan["temperature"],
        "stream": True, "timeout": plan["request_timeout_s"]}).encode()


def send(url, body: bytes, *, t0: float, deadline: float | None,
         timeout: float) -> dict:
    """POST one streaming completion and stamp every token as it arrives.
    ``deadline`` (absolute, monotonic): give up there and report the request
    as cut, not failed (the closed loop's end of window)."""
    out = {"sent": time.monotonic() - t0, "token_t": [], "ids": [],
           "ok": False, "cut": False, "error": None}
    conn = http.client.HTTPConnection(url.hostname, url.port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", body=body,
                     headers={"Content-Type": "application/json"})
        if deadline is not None:
            conn.sock.settimeout(max(0.05, deadline - time.monotonic()))
        resp = conn.getresponse()
        if resp.status != 200:
            out["error"] = f"HTTP {resp.status}: {resp.read(200)!r}"
            return out
        while True:
            if deadline is not None:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise socket.timeout()
                conn.sock.settimeout(left)
            line = resp.readline()
            if not line:
                out["error"] = "stream ended without [DONE]"
                return out
            if not line.startswith(b"data: "):
                continue
            data = line[6:].strip()
            if data == b"[DONE]":
                out["ok"] = True
                return out
            now = time.monotonic() - t0
            for tok in decode_ids(json.loads(data)["choices"][0]["text"]):
                out["token_t"].append(now)
                out["ids"].append(tok)
    except (socket.timeout, TimeoutError):
        if deadline is not None and time.monotonic() >= deadline - 0.01:
            out["cut"] = True
        else:
            out["error"] = "timed out"
        return out
    except (OSError, http.client.HTTPException, ValueError, KeyError) as exc:
        out["error"] = f"{type(exc).__name__}: {exc}"
        return out
    finally:
        out["end"] = time.monotonic() - t0
        conn.close()


def _finish(plan: dict, req: dict, res: dict) -> dict:
    """Keep what the reduction needs; check the answer's shape here, where
    the ids are."""
    ids = res.pop("ids")
    res.update(i=req["i"], due=req["due_s"], prompt_len=req["prompt_len"],
               max_tokens=req["max_tokens"], n_tokens=len(ids),
               ids_in_vocab=all(0 <= t < plan["vocab"] for t in ids))
    return res


def run_warmup(plan: dict, url) -> list[str]:
    """Stage after stage; the requests of one stage go out together. Returns
    the errors (a warm-up that fails fails the run)."""
    errors = []
    for s, stage in enumerate(plan["warmup"]):
        reqs = [{"i": 10**6 + 1000 * s + j, "due_s": 0.0, "prefix": -1,
                 "prompt_len": int(p), "max_tokens": int(o)}
                for j, (p, o) in enumerate(stage)]
        results: list = [None] * len(reqs)

        def one(j):
            results[j] = send(url, _body(plan, reqs[j]), t0=time.monotonic(),
                              deadline=None,
                              timeout=plan["request_timeout_s"])

        threads = [threading.Thread(target=one, args=(j,))
                   for j in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for req, res in zip(reqs, results):
            if not res["ok"] or len(res["ids"]) != req["max_tokens"]:
                errors.append(f"warm-up stage {s}: {req['prompt_len']}+"
                              f"{req['max_tokens']}: {res['error']} "
                              f"({len(res['ids'])} tokens)")
    return errors


def run_open_loop(plan: dict, url, t0: float) -> list[dict]:
    """One thread per request, each asleep until its due instant: nothing
    one request does can make the next one late except the interpreter."""
    reqs = plan["requests"]
    bodies = [_body(plan, r) for r in reqs]
    results: list = [None] * len(reqs)
    give_up = plan["seconds"] + plan["drain_timeout_s"]

    def one(j):
        wait = t0 + reqs[j]["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        results[j] = _finish(plan, reqs[j], send(
            url, bodies[j], t0=t0, deadline=None,
            timeout=max(1.0, give_up - reqs[j]["due_s"])))

    threads = [threading.Thread(target=one, args=(j,), daemon=True)
               for j in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(max(0.0, t0 + give_up + 5.0 - time.monotonic()))
    return [r if r is not None else
            {"i": reqs[j]["i"], "due": reqs[j]["due_s"], "ok": False,
             "cut": False, "error": "never returned", "token_t": [],
             "n_tokens": 0, "prompt_len": reqs[j]["prompt_len"],
             "max_tokens": reqs[j]["max_tokens"], "ids_in_vocab": True,
             "sent": reqs[j]["due_s"], "end": give_up}
            for j, r in enumerate(results)]


def run_closed_loop(plan: dict, url, t0: float) -> list[dict]:
    """``clients`` clients, each sending its next request when the last one
    ended, until the window ends; a request in flight then is cut."""
    reqs, n_clients = plan["requests"], plan["clients"]
    deadline = t0 + plan["seconds"]
    results: list[dict] = []
    lock = threading.Lock()

    def client(c):
        k = c
        while time.monotonic() < deadline:
            # Sizes repeat when the pool is walked round; contents never do
            # (the tokens come from the request's index), so a second lap
            # is not served from the prefix cache.
            req = dict(reqs[k % len(reqs)], i=k)
            req["due_s"] = time.monotonic() - t0
            res = _finish(plan, req, send(
                url, _body(plan, req), t0=t0, deadline=deadline,
                timeout=plan["request_timeout_s"]))
            with lock:
                results.append(res)
            k += n_clients

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(plan["seconds"] + 10.0)
    with lock:
        return list(results)


def main(argv) -> int:
    plan_path, out_path, base_url = argv[1], argv[2], argv[3]
    with open(plan_path) as f:
        plan = json.load(f)
    url = urlparse(base_url)
    warm_errors = run_warmup(plan, url)
    print("READY", flush=True)
    if not sys.stdin.readline().startswith("GO"):
        return 3
    t0 = time.monotonic()
    runner = run_open_loop if plan["kind"] == "open_loop" else run_closed_loop
    results = runner(plan, url, t0) if not warm_errors else []
    with open(out_path, "w") as f:
        json.dump({"t0": t0, "warmup_errors": warm_errors,
                   "results": results}, f)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
