"""The HTTP probe: a child process that creates pods over the API server's
REST surface open-loop, at a fixed rate, and times each request from when it
was due. It imports nothing but the standard library (above all not JAX:
the parent holds the chip).

    python3 benchmark/probe.py --url URL --rate R --salt S --namespace NS \
        --template '{"requests": {"cpu": "100m", "memory": "500Mi"}}'

The template is a configuration's (`deploy.TEMPLATE_FIELDS`): each pod
requests, and limits, its `requests`, and carries its `labels`,
`topologySpreadConstraints` and `affinity` where it states them.

It starts sending at once. A line "stop" on stdin ends the sending; it then
waits for every request in flight and prints one JSON line: every request
as [due, sent, done, status, name] on the monotonic clock (system-wide, so
the parent can place them against its window) and the schedule (`t0`,
`gap`: request i was due at t0 + i * gap, so one never sent shows as a
hole). A line "readback" then lists the namespace's pods over HTTP and
prints a second JSON line with the node each listed pod reads.
"""

from __future__ import annotations

import argparse
import http.client
import json
import sys
import threading
import time
import urllib.parse

THREADS = 256  # open loop at 50/s holds up to 5 s of latency in flight
TIMEOUT_S = 60.0


def pod_body(name: str, template: dict) -> bytes:
    requests = template["requests"]
    pod = {
        "apiVersion": "v1", "kind": "Pod",
        "metadata": {"name": name},
        "spec": {"containers": [{
            "name": "pause", "image": "registry.k8s.io/pause:3.10",
            "resources": {"requests": requests, "limits": requests}}]},
    }
    if template.get("labels"):
        pod["metadata"]["labels"] = template["labels"]
    for field in ("topologySpreadConstraints", "affinity"):
        if field in template:
            pod["spec"][field] = template[field]
    return json.dumps(pod).encode()


class Probe:
    def __init__(self, url: str, rate: float, salt: str, namespace: str,
                 template: dict):
        u = urllib.parse.urlparse(url)
        self.host, self.port = u.hostname, u.port
        self.gap = 1.0 / rate
        self.salt = salt
        self.ns = namespace
        self.template = template
        self.results: list = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._next = 0
        self.t0 = time.monotonic()

    def _take(self):
        with self._lock:
            if self._stop.is_set():
                return None
            i = self._next
            self._next += 1
        return i, self.t0 + i * self.gap

    def worker(self) -> None:
        conn = None
        path = f"/api/v1/namespaces/{self.ns}/pods"
        while True:
            job = self._take()
            if job is None:
                break
            i, due = job
            delay = due - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                break  # never sent
            name = f"p-{self.salt}-{i}"
            sent = time.monotonic()
            status = 0
            try:
                if conn is None:
                    conn = http.client.HTTPConnection(self.host, self.port,
                                                      timeout=TIMEOUT_S)
                conn.request("POST", path, body=pod_body(name, self.template),
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                status = resp.status
            except (OSError, http.client.HTTPException):
                status = -1
                if conn is not None:
                    conn.close()
                conn = None
            with self._lock:
                self.results.append([due, sent, time.monotonic(), status, name])
        if conn is not None:
            conn.close()

    def readback(self) -> dict:
        conn = http.client.HTTPConnection(self.host, self.port, timeout=TIMEOUT_S)
        try:
            conn.request("GET", f"/api/v1/namespaces/{self.ns}/pods")
            resp = conn.getresponse()
            body = json.loads(resp.read())
        finally:
            conn.close()
        return {it["metadata"]["name"]: (it.get("spec") or {}).get("nodeName")
                for it in body.get("items", [])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--url", required=True)
    ap.add_argument("--rate", type=float, required=True)
    ap.add_argument("--salt", required=True)
    ap.add_argument("--namespace", required=True)
    ap.add_argument("--template", required=True, help="JSON pod template")
    args = ap.parse_args(argv)
    probe = Probe(args.url, args.rate, args.salt, args.namespace,
                  json.loads(args.template))
    threads = [threading.Thread(target=probe.worker, daemon=True)
               for _ in range(THREADS)]
    for t in threads:
        t.start()
    for line in sys.stdin:
        if line.strip() == "stop":
            break
    probe._stop.set()
    for t in threads:
        t.join(timeout=TIMEOUT_S + 5)
    with probe._lock:
        results = list(probe.results)
    print(json.dumps({"t0": probe.t0, "gap": probe.gap, "results": results,
                      "stuck_threads": sum(t.is_alive() for t in threads)}),
          flush=True)
    for line in sys.stdin:
        if line.strip() == "readback":
            break
    try:
        nodes = probe.readback()
        err = ""
    except (OSError, http.client.HTTPException, ValueError) as e:
        nodes, err = {}, f"{type(e).__name__}: {e}"
    print(json.dumps({"nodes": nodes, "readback_error": err}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
