"""One load-generator process: HTTP only, NumPy and the standard library.

    python3 loadgen.py <spec.json>

It never imports JAX: the chip has one owner, the server process.  The
spec names one stream of the traffic mix (``kind`` and its parameters), the
run's seed, this process's index among the stream's processes, and the
output path.  The process builds its request bodies from the seed, saves
the values it will send (``<out>.pool.npz``, for the reference), prints
``ready``, then reads one JSON line from stdin: ``{"url", "t0", "seconds"}``
with ``t0`` on the shared monotonic clock.  It sends from ``t0`` for
``seconds``, waits for every reply (a minute past the close at most),
writes ``<out>.npz`` and prints ``done``.

Stream kinds:

* ``ingest_closed`` -- ``senders`` threads, each POSTs its next body when
  the previous one was answered; a 429 is honoured for the server's
  ``X-Retry-After-Ms`` and the same body is sent again.
* ``ingest_open``   -- POSTs due on a fixed schedule (``values_per_s``);
  each is timed from its due time.
* ``reads_open``    -- GETs due on a fixed schedule (``requests_per_s``)
  over the ``mix`` of routes; a seeded sample keeps its answer.
* ``probes``        -- one single-value POST per ``1 / probes_per_s`` on
  ``probe_keys`` keys with rising values, each followed by polls every
  ``poll_interval_s`` until an answer reflects it.
"""

from __future__ import annotations

import http.client
import json
import queue
import sys
import threading
import time
from pathlib import Path
from urllib.parse import quote, urlparse

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))
import stream  # noqa: E402

TIMEOUT_S = 60.0  # per request, and the wait past the close


def _request(host: str, port: int, method: str, path: str, body: bytes | None = None):
    """(status, headers dict, body bytes); status 0 on a connection error."""
    conn = http.client.HTTPConnection(host, port, timeout=TIMEOUT_S)
    try:
        headers = {"Content-Type": "application/json"} if body is not None else {}
        conn.request(method, path, body=body, headers=headers)
        resp = conn.getresponse()
        data = resp.read()
        return resp.status, {k.lower(): v for k, v in resp.getheaders()}, data
    except (OSError, http.client.HTTPException):
        return 0, {}, b""
    finally:
        conn.close()


def _retry_s(headers: dict) -> float:
    try:
        return float(headers.get("x-retry-after-ms", "10")) / 1e3
    except ValueError:
        return 0.01


class Recorder:
    """Thread-safe append-only columns of one stream's request records."""

    def __init__(self, *cols: str):
        self.cols = {c: [] for c in cols}
        self.lock = threading.Lock()

    def add(self, **row) -> None:
        with self.lock:
            for c, v in row.items():
                self.cols[c].append(v)

    def arrays(self) -> dict:
        return {c: np.asarray(v) for c, v in self.cols.items()}


def _pool(spec: dict, salt: int):
    s = spec["stream"]
    return stream.make_pool(
        s["bodies_per_process"], stream.key_probs(spec["num_keys"], spec["seed"]),
        s["values_per_post"],
        spec["families"], stream.sub_seed(spec["seed"], salt, spec["proc"]))


# --------------------------------------------------------------------- #
def run_ingest_closed(spec, host, port, t0, t_end, keys, vals, bodies):
    s = spec["stream"]
    rec = Recorder("body", "t_send", "t_done", "status", "n")
    n_bodies = len(bodies)

    def sender(i: int) -> None:
        j = i
        while time.monotonic() < t_end:
            b = j % n_bodies
            t_send = time.monotonic()
            status, headers, _ = _request(host, port, "POST", "/ingest", bodies[b])
            rec.add(body=b, t_send=t_send, t_done=time.monotonic(), status=status,
                    n=vals[b].size)
            if status == 429:
                time.sleep(_retry_s(headers))
                continue  # the same body again
            j += s["senders"]

    while time.monotonic() < t0:
        time.sleep(min(0.01, max(0.0, t0 - time.monotonic())))
    threads = [threading.Thread(target=sender, args=(i,), daemon=True)
               for i in range(s["senders"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join(t_end - time.monotonic() + TIMEOUT_S)
    return rec.arrays()


def _open_loop(due: np.ndarray, workers: int, job) -> None:
    """Hand each due time to a free worker; late workers make late sends."""
    q: queue.Queue = queue.Queue()

    def worker() -> None:
        while True:
            item = q.get()
            if item is None:
                return
            job(*item)

    threads = [threading.Thread(target=worker, daemon=True) for _ in range(workers)]
    for t in threads:
        t.start()
    for i, t_due in enumerate(due.tolist()):
        wait = t_due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        q.put((i, t_due))
    for _ in threads:
        q.put(None)
    deadline = float(due[-1]) + TIMEOUT_S
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))


def _schedule(spec, rate: float, t0: float, seconds: float, salt: int) -> np.ndarray:
    """This process's share of a stream's open-loop due times."""
    nprocs = spec["nprocs"]
    rng = np.random.default_rng(stream.sub_seed(spec["seed"], salt, spec["proc"]))
    return t0 + stream.arrivals(rate / nprocs, seconds, rng)


def run_ingest_open(spec, host, port, t0, seconds, keys, vals, bodies):
    s = spec["stream"]
    mean_n = float(np.mean([v.size for v in vals]))
    due = _schedule(spec, s["values_per_s"] / mean_n, t0, seconds, 11)
    rec = Recorder("body", "t_due", "t_send", "t_done", "status", "n")

    def job(i: int, t_due: float) -> None:
        b = i % len(bodies)
        while True:
            t_send = time.monotonic()
            status, headers, _ = _request(host, port, "POST", "/ingest", bodies[b])
            rec.add(body=b, t_due=t_due, t_send=t_send, t_done=time.monotonic(),
                    status=status, n=vals[b].size)
            if status != 429:
                return
            time.sleep(_retry_s(headers))

    _open_loop(due, s["workers"], job)
    return rec.arrays()


def _qparam(qs) -> str:
    return ",".join(repr(float(q)) for q in qs)


def read_paths(spec, n: int, rng) -> tuple[list[str], np.ndarray]:
    """The GET path of each read: route by the mix's shares (stratified),
    q-set alternating, per-key reads on a Zipf key."""
    s = spec["stream"]
    shares = np.asarray([m["share"] for m in s["mix"]], np.float64)
    cdf = np.cumsum(shares / shares.sum())
    cdf[-1] = 1.0
    route = np.searchsorted(cdf, stream.stratified(n, rng), side="right")
    keys = stream.body_keys(n, stream.key_probs(spec["num_keys"], spec["seed"]), rng)
    paths = []
    for i, (r, k) in enumerate(zip(route.tolist(), keys.tolist())):
        m = s["mix"][r]
        qs = s["qsets"][i % len(s["qsets"])]
        p = f"/{m['route']}?q={_qparam(qs)}"
        if m["route"] == "quantiles":
            p += f"&endpoint={quote(stream.key_name(k))}"
        if "window" in m:
            p += f"&window={m['window']}"
        paths.append(p)
    return paths, route


def run_reads_open(spec, host, port, t0, seconds):
    s = spec["stream"]
    due = _schedule(spec, s["requests_per_s"], t0, seconds, 12)
    rng = np.random.default_rng(stream.sub_seed(spec["seed"], 13, spec["proc"]))
    paths, route = read_paths(spec, due.size, rng)
    keep = set(rng.choice(due.size, size=min(due.size, s["sample_answers"]),
                          replace=False).tolist())
    rec = Recorder("idx", "route", "t_due", "t_send", "t_done", "status")
    answers: dict[int, tuple[str, str, str]] = {}

    def job(i: int, t_due: float) -> None:
        t_send = time.monotonic()
        status, headers, data = _request(host, port, "GET", paths[i])
        rec.add(idx=i, route=int(route[i]), t_due=t_due, t_send=t_send,
                t_done=time.monotonic(), status=status)
        if i in keep and status == 200:
            answers[i] = (paths[i], headers.get("etag", ""), data.decode())

    _open_loop(due, s["workers"], job)
    out = rec.arrays()
    out["answers"] = np.asarray(json.dumps(answers))
    return out


def run_probes(spec, host, port, t0, seconds):
    """Probe ``i`` goes to key ``i % probe_keys`` with value
    ``first_value * growth ** (i // probe_keys)``: every probe is its key's
    new maximum, one ``growth`` factor above the last, so ``q=1.0`` over
    the key's last two slices reflects it once it is queryable."""
    s = spec["stream"]
    nprocs, proc = spec["nprocs"], spec["proc"]
    n = int(round(s["probes_per_s"] * seconds))
    due_all = t0 + np.arange(n) / s["probes_per_s"]
    mine = np.arange(proc, n, nprocs)
    rec = Recorder("idx", "t_due", "t_ack", "t_seen", "polls", "status")
    cut = s["growth"] ** -0.5  # between a probe and its key's last one

    def job(j: int, t_due: float) -> None:
        i = int(mine[j])
        key = f"/p{i % s['probe_keys']}"
        value = s["first_value"] * s["growth"] ** (i // s["probe_keys"])
        body = json.dumps({"key": key, "values": [value]}).encode()
        while True:
            status, headers, _ = _request(host, port, "POST", "/ingest", body)
            if status != 429:
                break
            time.sleep(_retry_s(headers))
        t_ack = time.monotonic()
        t_seen, polls = float("nan"), 0
        path = f"/quantiles?endpoint={quote(key)}&slices={s['slices']}&q=1.0"
        while status == 200 and time.monotonic() < t_due + TIMEOUT_S:
            polls += 1
            st, _, data = _request(host, port, "GET", path)
            if st == 200:
                got = json.loads(data)["quantiles"][0]
                if got is not None and got >= value * cut:
                    t_seen = time.monotonic()
                    break
            elif st != 404:  # 404: the key is not queryable yet
                status = st
                break
            time.sleep(s["poll_interval_s"])
        rec.add(idx=i, t_due=t_due, t_ack=t_ack, t_seen=t_seen, polls=polls,
                status=status)

    _open_loop(due_all[mine], s["workers"], job)
    return rec.arrays()


def main(argv=None) -> int:
    spec = json.loads(Path((argv or sys.argv)[1]).read_text())
    kind = spec["stream"]["kind"]
    out = Path(spec["out"])
    keys = vals = bodies = None
    if kind in ("ingest_closed", "ingest_open"):
        keys, vals, bodies = _pool(spec, 10)
        sizes = np.asarray([v.size for v in vals])
        np.savez(str(out) + ".pool.npz", keys=keys, sizes=sizes,
                 values=np.concatenate(vals))
    print("ready", flush=True)
    go = json.loads(sys.stdin.readline())
    url = urlparse(go["url"])
    host, port, t0, seconds = url.hostname, url.port, go["t0"], go["seconds"]
    if kind == "ingest_closed":
        res = run_ingest_closed(spec, host, port, t0, t0 + seconds, keys, vals, bodies)
    elif kind == "ingest_open":
        res = run_ingest_open(spec, host, port, t0, seconds, keys, vals, bodies)
    elif kind == "reads_open":
        res = run_reads_open(spec, host, port, t0, seconds)
    elif kind == "probes":
        res = run_probes(spec, host, port, t0, seconds)
    else:
        raise ValueError(f"unknown stream kind {kind!r}")
    np.savez(str(out) + ".npz", **res)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
