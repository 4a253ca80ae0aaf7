"""The one traffic generator, and the loops that drive a system with it.

A traffic mix is a JSON file under ``bench/traffic`` (the ``why`` of its
cell in ``BENCHMARK.json`` says why it exists).
Its keys:

* ``loop``: ``"open"`` (requests are sent when due, whatever is still
  running: independent users) or ``"closed"`` (``clients`` callers, each
  sending its next request when the last one returned);
* ``arrivals`` (open loop): ``{"dist": "poisson", "rate_per_s": r}``;
* ``fields``: one entry per request parameter the system reads, each a
  distribution: ``fixed`` (``value``), ``lognormal`` (``median``,
  ``sigma``, ``min``, ``max``; rounded to whole numbers), ``uniform_int``
  (``low``, ``high``; values that change no amount of work, such as token
  ids), ``cycle`` (``n``: request ``i`` gets ``i % n``);
* anything else is for the system adapter (batch limits, sizes).

Every seed gets the same work, spread the same way over the window. The
requests fall into blocks of ``BLOCK`` in a row; in every block the sizes
and the gaps between arrivals are the quantiles ``(j + 0.5) / BLOCK`` of
their distribution, and the seed only shuffles their order within the
block. Only values that change no work (token ids) are drawn freely. So
two seeds differ in which request comes when inside a few seconds, not in
how much work there is nor where in the window it lies.
"""
from __future__ import annotations

import contextlib
import math
import threading
import time
from concurrent.futures import Future
from statistics import NormalDist
from typing import Callable, List, Tuple

import numpy as np

#: the pool of requests a closed loop cycles through
CLOSED_POOL = 4096
#: requests per block: each block holds the same sizes and gaps
BLOCK = 32


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """A generator of its own for each use of the seed."""
    return np.random.default_rng([seed, *map(ord, stream)])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def blocked(per_block: np.ndarray, n: int,
            rng: np.random.Generator) -> np.ndarray:
    """``n`` values: block after block of ``per_block``, each block in an
    order of its own drawn from ``rng``."""
    blocks = -(-n // per_block.shape[0])
    return np.concatenate([rng.permutation(per_block)
                           for _ in range(blocks)])[:n]


def draw(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        return np.full(n, spec["value"])
    if dist == "cycle":
        return np.arange(n) % int(spec["n"])
    if dist == "uniform_int":
        return rng.integers(int(spec["low"]), int(spec["high"]), n)
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(q) for q in quantiles(BLOCK)])
        x = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
        x = np.clip(x, spec["min"], spec["max"]).astype(np.int64)
        return blocked(x, n, rng)
    raise ValueError(f"unknown distribution {dist!r}")


def schedule(traffic: dict, seed: int, seconds: float) -> List[dict]:
    """The requests of one run. Open loop: those due in ``[0, seconds)``,
    each with ``due`` (seconds after the window opens). Closed loop: a
    pool the clients take in turn (client ``c``'s ``i``-th request is
    ``pool[(i * clients + c) % len(pool)]``)."""
    if traffic["loop"] == "open":
        arr = traffic["arrivals"]
        if arr["dist"] != "poisson":
            raise ValueError(f"unknown arrivals {arr['dist']!r}")
        rate = float(arr["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        gaps = blocked(-np.log1p(-quantiles(BLOCK)) / rate, n,
                       rng_for(seed, "gaps"))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    elif traffic["loop"] == "closed":
        n = CLOSED_POOL
        due = None
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    fields = {k: draw(spec, n, rng_for(seed, k))
              for k, spec in traffic.get("fields", {}).items()}
    reqs = []
    for i in range(n):
        r = {k: int(v[i]) for k, v in fields.items()}
        r["i"] = i
        if due is not None:
            r["due"] = float(due[i])
        reqs.append(r)
    return reqs


# ----------------------------------------------------------------------------
# loops
# ----------------------------------------------------------------------------
Span = Callable[[str], contextlib.AbstractContextManager]


def no_span(name: str):
    return contextlib.nullcontext()


def drive_closed(issue: Callable[[dict], dict], reqs: List[dict],
                 clients: int, seconds: float, span: Span = no_span
                 ) -> Tuple[List[dict], Tuple[float, float]]:
    """``clients`` threads, each issuing its next request when the last
    returned, until ``seconds`` have passed. The window ends when the last
    request started inside it completes."""
    records: List[dict] = []
    lock = threading.Lock()
    t0 = time.monotonic()
    stop_at = t0 + seconds

    def client(c: int) -> None:
        i = 0
        while True:
            start = time.monotonic()
            if start >= stop_at:
                return
            req = reqs[(i * clients + c) % len(reqs)]
            i += 1
            rec = {"req": req, "due": start, "start": start}
            try:
                with span("bench.ask"):
                    rec.update(issue(req))
                rec["ok"] = True
            except Exception as exc:  # a failed request is a result
                rec["ok"], rec["error"] = False, repr(exc)
            rec.setdefault("end", time.monotonic())
            with lock:
                records.append(rec)
            if not rec["ok"]:
                return

    threads = [threading.Thread(target=client, args=(c,), name=f"client-{c}")
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t1 = max((r["end"] for r in records), default=time.monotonic())
    return records, (t0, t1)


def drive_open(submit: Callable[[dict], Future], reqs: List[dict],
               seconds: float, drain_s: float, span: Span = no_span
               ) -> Tuple[List[dict], Tuple[float, float], float]:
    """Send each request at its due time; then wait for every one, at most
    ``drain_s`` past the close. → records, window, and how late the
    generator sent its latest request (seconds)."""
    t0 = time.monotonic() + 0.005
    pending: List[Tuple[dict, Future]] = []
    late = 0.0
    for req in reqs:
        due = t0 + req["due"]
        wait = due - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        start = time.monotonic()
        late = max(late, start - due)
        rec = {"req": req, "due": due, "start": start}
        with span("bench.submit"):
            try:
                pending.append((rec, submit(req)))
            except Exception as exc:
                rec["ok"], rec["error"] = False, repr(exc)
                pending.append((rec, None))
    close = t0 + seconds
    for rec, fut in pending:
        if fut is None:
            continue
        try:
            rec.update(fut.result(timeout=max(0.0, close + drain_s
                                               - time.monotonic())))
            rec["ok"] = True
        except Exception as exc:
            rec["ok"], rec["error"] = False, repr(exc)
    records = [rec for rec, _ in pending]
    t1 = max([close] + [r["end"] for r in records if r.get("ok")])
    return records, (t0, t1), late


# ----------------------------------------------------------------------------
def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile; a missing value is ``inf`` and counts."""
    if not values:
        return math.inf
    xs = sorted(values)
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]
