#!/usr/bin/env python3
"""Closed-loop load generator for the serving cells. Standard library only:
it runs as a child process that never imports JAX, so it cannot take the
chip, and its work does not sit in the server's interpreter lock.

    python loadgen.py SPEC.json

SPEC: {"port", "path", "seconds", "num", "callers": [[word, ...], ...],
"keep_every", "out", "once"}. Each caller holds one keep-alive connection,
sends ``POST path {"word": w, "num": num}``, waits for the whole reply, and
sends its next word (its list, in order, wrapping around) until ``seconds``
have passed; a request in flight then is finished and counted. With
``once`` a caller stops at the end of its list (the cache warm-up). Latency
is the caller's own clock from before the send to after the last byte.
Written to ``out``: the window's length, one [start_offset_s, latency_s,
status] per request, and the body of every ``keep_every``-th request of each
caller for the correctness check.
"""

import http.client
import json
import sys
import threading
import time


def caller(k, words, spec, t_end, out):
    conn = http.client.HTTPConnection("127.0.0.1", spec["port"], timeout=60)
    rows, kept = [], []
    i = 0
    t_base = spec["_t0"]
    while True:
        t0 = time.perf_counter()
        if t0 >= t_end or (spec.get("once") and i >= len(words)):
            break
        w = words[i % len(words)]
        body = json.dumps({"word": w, "num": spec["num"]})
        status, raw = 0, b""
        try:
            conn.request("POST", spec["path"], body,
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            raw = r.read()
            status = r.status
        except Exception as e:  # counted as failed; reconnect
            raw = repr(e).encode()
            conn.close()
            conn = http.client.HTTPConnection(
                "127.0.0.1", spec["port"], timeout=60)
        t1 = time.perf_counter()
        rows.append([t0 - t_base, t1 - t0, status])
        if i % spec["keep_every"] == k % spec["keep_every"]:
            kept.append({"caller": k, "ordinal": i, "word": w,
                         "status": status,
                         "body": raw.decode("utf-8", "replace")})
        i += 1
    conn.close()
    out[k] = (rows, kept)


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    n = len(spec["callers"])
    out = [None] * n
    spec["_t0"] = time.perf_counter()
    wall0 = time.time()
    t_end = spec["_t0"] + float(spec["seconds"])
    threads = [
        threading.Thread(target=caller,
                         args=(k, spec["callers"][k], spec, t_end, out))
        for k in range(n)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t1 = time.perf_counter()
    doc = {
        "wall_start": wall0,
        "window_s": t1 - spec["_t0"],
        "requests": [r for rows, _ in out for r in rows],
        "kept": [x for _, kept in out for x in kept],
    }
    with open(spec["out"], "w") as f:
        json.dump(doc, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
