"""Closed-loop load from one process, and the arithmetic of its completion
log.  One general generator: everything a traffic mix fixes (clients, the
mix of queries, each query's parameters and how many of their values a run
sends, the request timeout) is read from its data file."""

import dataclasses
import datetime
import random
import threading
import time
from typing import Callable, Dict, List, Optional


@dataclasses.dataclass
class Request:
    client: int
    query: str
    params: dict
    t_submit: float                  # perf_counter before svc.submit
    t_submitted: float = 0.0         # ... after it returned
    t_done: Optional[float] = None   # to_df returned; None: never answered
    t_end: float = 0.0               # the client stopped waiting (either way)
    error: Optional[str] = None
    answer: object = None            # the pandas frame
    queue_s: Optional[float] = None  # handle.timings()
    run_s: Optional[float] = None
    tasks: Optional[int] = None      # handle.latency_stats()["count"]
    ok: bool = False                 # answered, in time and correct

    @property
    def latency_s(self) -> Optional[float]:
        return None if self.t_done is None else self.t_done - self.t_submit


def values(kind: str, spec):
    """Every value a parameter can take, in a fixed order.  ``int_range``
    and ``date_range`` include both ends; ``choice`` lists its values."""
    if kind == "choice":
        return list(spec)
    if kind == "int_range":
        return list(range(spec[0], spec[1] + 1))
    if kind == "date_range":
        first, last = (datetime.date.fromisoformat(d) for d in spec)
        return [(first + datetime.timedelta(days=i)).isoformat()
                for i in range((last - first).days + 1)]
    raise ValueError(f"unknown kind of parameter values: {kind!r}")


def plan(traffic: dict, seed: int) -> Dict[str, List[dict]]:
    """Per query of the mix, the parameter sets this run sends: every
    combination of its parameters' values (``params`` of the traffic file:
    query -> parameter -> {kind: spec}), shuffled by the seed, the first
    ``values_per_run`` of them (all, where the file gives none).  A query
    without parameters has the one empty set."""
    out = {}
    for i, query in enumerate(sorted(traffic["mix"])):
        grid = [{}]
        for name, how in sorted(traffic.get("params", {}).get(
                query, {}).items()):
            (kind, spec), = how.items()
            grid = [dict(g, **{name: v}) for g in grid
                    for v in values(kind, spec)]
        random.Random(seed * 7919 + i).shuffle(grid)
        out[query] = grid[:traffic.get("values_per_run", {}).get(query)]
    return out


def schedule(mix: Dict[str, float], sets: Dict[str, List[dict]], seed: int,
             client: int, clients: int):
    """Endless draw of (query, parameters) for one client, fixed by the
    seed: the query by the mix's weights, its parameter sets in turn, each
    client starting at its own place in the run's list."""
    rng = random.Random(seed * 1009 + client)
    names, weights = zip(*sorted(mix.items()))
    at = {q: client * -(-len(sets[q]) // clients) for q in names}
    while True:
        query = rng.choices(names, weights)[0]
        yield query, sets[query][at[query] % len(sets[query])]
        at[query] += 1


def warm_up_pass(sets: Dict[str, List[dict]], client: int, clients: int):
    """Every (query, parameters) of the run once, dealt over the clients in
    turn, and again from the start until every client has one to send."""
    items = [(q, p) for q in sorted(sets) for p in sets[q]]
    n = max(len(items), clients)
    return [items[i % len(items)] for i in range(client, n, clients)]


def run_closed(submit: Callable[[str, dict], object], clients: int, draws,
               timeout_s: float,
               seconds: Optional[float] = None) -> List[Request]:
    """``clients`` threads, each sending its next request the moment its
    last answer is in host memory.  ``draws(client)`` yields (query,
    parameters); a client runs until its draws end or ``seconds`` have
    passed since the first submit (requests in flight are waited for, each
    up to ``timeout_s``).  ``submit(query, parameters)`` returns a handle
    with ``to_df``, ``timings`` and ``latency_stats``."""
    log: List[Request] = []
    lock = threading.Lock()
    start = threading.Barrier(clients)

    def client_loop(idx: int) -> None:
        mine = iter(draws(idx))
        start.wait()
        opened = time.perf_counter()
        for query, params in mine:
            now = time.perf_counter()
            if seconds is not None and now - opened >= seconds:
                return
            req = Request(client=idx, query=query, params=params,
                          t_submit=now)
            with lock:
                log.append(req)
            handle = None
            try:
                handle = submit(query, params)
                req.t_submitted = time.perf_counter()
                req.answer = handle.to_df(timeout=timeout_s)
                req.t_done = time.perf_counter()
                timings = handle.timings()
                req.queue_s, req.run_s = timings["queue_s"], timings["run_s"]
                req.tasks = (handle.latency_stats() or {}).get("count")
            except Exception as e:  # noqa: BLE001 — a failed request is
                # counted, never fatal to the window
                req.error = f"{type(e).__name__}: {e}"[:300]
                if handle is not None and req.t_done is None:
                    try:
                        handle.cancel(wait=False)
                    except Exception:  # noqa: BLE001
                        pass
            finally:
                req.t_end = time.perf_counter()

    threads = [threading.Thread(target=client_loop, args=(i,),
                                name=f"bench-client-{i}")
               for i in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return log


# -- arithmetic over a completion log ---------------------------------------


def window_s(log: List[Request]) -> float:
    """First submit to the end of the last request submitted in the window:
    its answer, or the moment its client gave up waiting."""
    if not log:
        return 0.0
    return max(r.t_end for r in log) - min(r.t_submit for r in log)


def queries_per_s(log: List[Request]) -> Optional[float]:
    span = window_s(log)
    good = sum(r.ok for r in log)
    return good / span if span > 0 and good else None


def latencies_ms(log: List[Request]) -> List[float]:
    """Latency of every request answered correctly and in time; failed
    requests are in ``attempted`` and ``failed`` and in no latency."""
    return [1e3 * r.latency_s for r in log if r.ok]


def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear interpolation between closest ranks, over all values."""
    if not values:
        return None
    s = sorted(values)
    pos = q / 100.0 * (len(s) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)
