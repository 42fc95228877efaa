#!/usr/bin/env python
"""Poll a live apex_tpu metrics plane and record what it saw.

    python tools/metrics_probe.py --port P --out DIR [--host H]
        [--interval S] [--timeout S] [--settle N]

The external half of the ci.sh step-15 smoke: started BEFORE the
serve (``standalone_gpt --serve[-fleet] --metrics-port P``), it polls
``/healthz`` + ``/metrics`` + ``/varz`` until the server goes away
(``--settle`` consecutive connection failures after at least one
success) or ``--timeout`` expires, then writes:

- ``DIR/healthz.log`` — one line per *observed status-code change*
  (``<code> <body>``), so a drain shows up as the ``200 -> 503``
  transition an operator's prober would alert on;
- ``DIR/metrics.last`` / ``DIR/varz.last`` — the last successfully
  scraped bodies (the exposition document / snapshot JSON to assert
  against).

Stdlib only (urllib): the probe must run anywhere CI does.  Exits 0
iff at least one scrape of every endpoint succeeded.
"""
import argparse
import json
import os
import sys
import time
import urllib.error
import urllib.request


def _get(url: str, timeout: float):
    """Return (status_code, body) — HTTP errors like the 503 drain
    are observations, not failures; only transport errors raise."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.getcode(), r.read().decode("utf-8", "replace")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8", "replace")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--out", required=True, metavar="DIR")
    p.add_argument("--interval", type=float, default=0.05,
                   help="poll period in seconds (default 0.05)")
    p.add_argument("--timeout", type=float, default=120.0,
                   help="give up after this many seconds total")
    p.add_argument("--settle", type=int, default=10,
                   help="consecutive connection failures AFTER a "
                        "success that mean the server is gone")
    args = p.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    base = f"http://{args.host}:{args.port}"
    deadline = time.monotonic() + args.timeout
    transitions = []          # (code, body) on status-code change
    last_code = None
    bodies = {}               # endpoint -> last good body
    connected = False
    misses = 0
    while time.monotonic() < deadline:
        try:
            code, body = _get(f"{base}/healthz", args.interval + 1.0)
            connected, misses = True, 0
            if code != last_code:
                transitions.append((code, body.strip()))
                last_code = code
            for ep in ("metrics", "varz"):
                _, b = _get(f"{base}/{ep}", args.interval + 1.0)
                bodies[ep] = b
        except (urllib.error.URLError, ConnectionError, OSError):
            misses += 1
            if connected and misses >= args.settle:
                break         # the serve tore the server down
        time.sleep(args.interval)
    with open(os.path.join(args.out, "healthz.log"), "w") as f:
        for code, body in transitions:
            f.write(f"{code} {body}\n")
    for ep in ("metrics", "varz"):
        if ep in bodies:
            with open(os.path.join(args.out, f"{ep}.last"),
                      "w") as f:
                f.write(bodies[ep])
    summary = {"transitions": [c for c, _ in transitions],
               "scraped": sorted(bodies),
               "connected": connected}
    print(f"[metrics-probe] {json.dumps(summary, sort_keys=True)}")
    if not (connected and len(bodies) == 2 and transitions):
        print("[metrics-probe] FAIL: never scraped all endpoints",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
