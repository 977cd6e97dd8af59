"""The load generator: a process of its own that never imports JAX (the chip
belongs to the parent, and so does the parent's interpreter lock).

    python3 -m benchmark.loadgen <plan.json> <results.json> <url> \
        [<tail_plan.json> <tail_results.json>]

Protocol on the standard streams: it sends the plan's warm-up stages, prints
``READY``, waits for a line on stdin (``GO``), takes t0 on the system-wide
monotonic clock, runs the measured phase, writes the results and prints
``DONE``. Every time it records is seconds after t0. Given a tail plan
(``--trace 2``: the traffic that is traced once the window has closed) it
then waits for one more line (``TAIL``), takes a new t0, runs the tail plan
until its ``seconds`` are up, cuts what is in flight then, writes the tail's
results and prints ``TAILDONE``; without one it ends after ``DONE`` as it
always did.

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


def run_open_loop(plan: dict, url, t0: float, cut: bool = False
                  ) -> list[dict]:
    """One thread per request, each asleep until its due instant: nothing
    one request does can make the next one late except the interpreter.
    ``cut`` (the tail): a request in flight when the plan's seconds are up
    is cut there instead of drained."""
    reqs = plan["requests"]
    bodies = [_body(plan, r) for r in reqs]
    results: list = [None] * len(reqs)
    give_up = plan["seconds"] + (0.0 if cut else plan["drain_timeout_s"])
    deadline = t0 + plan["seconds"] if cut else None

    def one(j):
        wait = t0 + reqs[j]["due_s"] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        results[j] = _finish(plan, reqs[j], send(
            url, bodies[j], t0=t0, deadline=deadline,
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
    base = plan.get("index_base", 0)    # the tail's indices start elsewhere
    deadline = t0 + plan["seconds"]
    results: list[dict] = []
    lock = threading.Lock()

    def client(c):
        k = c
        while time.monotonic() < deadline:
            # Sizes repeat when the pool is walked round; contents never do
            # (the tokens come from the request's index), so a second lap
            # is not served from the prefix cache.
            req = dict(reqs[k % len(reqs)], i=base + k)
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


def run_phase(plan_path: str, out_path: str, url, word: str, *,
              tail: bool) -> bool:
    """Load a plan, wait for ``word`` on stdin, run the plan and write its
    results; False if another word (or none) came. The measured phase
    warms up first and says ``READY``; the tail does neither, and cuts
    what is in flight when its seconds are up."""
    with open(plan_path) as f:
        plan = json.load(f)
    warm_errors = []
    if not tail:
        warm_errors = run_warmup(plan, url)
        print("READY", flush=True)
    if not sys.stdin.readline().startswith(word):
        return False
    t0 = time.monotonic()
    if warm_errors:
        results = []
    elif plan["kind"] == "open_loop":
        results = run_open_loop(plan, url, t0, cut=tail)
    else:
        results = run_closed_loop(plan, url, t0)
    with open(out_path, "w") as f:
        json.dump({"t0": t0, "warmup_errors": warm_errors,
                   "results": results}, f)
    return True


def main(argv) -> int:
    plan_path, out_path, base_url = argv[1], argv[2], argv[3]
    url = urlparse(base_url)
    if not run_phase(plan_path, out_path, url, "GO", tail=False):
        return 3
    print("DONE", flush=True)
    if len(argv) > 5 and run_phase(argv[4], argv[5], url, "TAIL",
                                   tail=True):
        print("TAILDONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
