"""Drift traffic: AWAPart's master loop (paper Fig. 6) under an open loop.

The traffic file lists phases, which split the window evenly. Each phase
names the queries of its adaptation round (``adapt``) and the mix its
requests are drawn from (``mix``); a round's queries carry their mix
weight as their frequency.

Set-up bootstraps on the ``bootstrap`` mix and serves one window of it. It
warms every join shape of the phases' pools by running each pool query's
plan through the executor directly, so the result cache stays empty, and
warms every round on a twin service over the same store, which drains each
session before its next round as the window does. Every request is one
``serve_window`` call of one query, as a SPARQL endpoint serves one query
per request (W3C SPARQL 1.1 Protocol, section 2.1), so the federation
count runs at the shape of a single query's matches, which set-up warms.

The window opens at the first phase's onset. At each phase's onset the
loop finishes the pending migration session, one ``svc.step()`` at a time,
and runs that phase's round. Between onsets each pass waits until a
request is due, applies one migration chunk while a session is pending,
and serves every due request. Requests due before the close that are still
waiting then are served after it, their wait counted. A request's latency
runs from its due time to the end of the ``serve_window`` call that
answered it.
"""
from __future__ import annotations

import dataclasses
import time
import traceback
from typing import Dict, List, Optional, Tuple

import numpy as np

from chipbench import deploy


@dataclasses.dataclass
class Window:
    """One pass of the loop."""
    start_s: float
    n: int
    misses: int
    epoch: int
    round: int                              # the round whose session it stepped
    step_ms: Optional[float]
    serve_ms: float


@dataclasses.dataclass
class Round:
    """One phase's adaptation round and its migration session."""
    onset_s: float
    end_s: float                            # the next onset, or the close
    pre_epoch: int
    accepted: bool
    reason: str
    plan_bytes: int
    n_chunks: int
    adapt_ms: float
    chunks: List[Tuple[int, int, int]]      # (epoch before, after, bytes)
    drained_s: Optional[float] = None
    forced: bool = False                    # finished at the next onset


@dataclasses.dataclass
class Run:
    """What the window did, for the metrics and the comparison."""
    names: List[str]
    due_s: np.ndarray
    done_s: np.ndarray                      # nan where never answered
    epoch: np.ndarray                       # epoch each request was served at
    answers: List[Optional[Tuple[dict, object]]]
    errors: List[str]
    plans: Dict[Tuple[str, int], Tuple[tuple, int]]   # (order, ppn)
    layouts: Dict[int, np.ndarray]          # epoch -> shard of every row
    rounds: List[Round]
    drained: bool
    chunk_ms: List[float]
    windows: List[Window]
    seconds: float
    step_errors: int = 0

    @property
    def latencies_ms(self) -> np.ndarray:
        return (self.done_s - self.due_s) * 1e3


def _mix(phase: dict) -> List[Tuple[str, float]]:
    return [(m["query"], float(m["weight"])) for m in phase["mix"]]


def mixes(traffic: dict) -> List[List[Tuple[str, float]]]:
    """Each phase's mix of (query, weight), for the arrival generator."""
    return [_mix(p) for p in traffic["phases"]]


def round_queries(dep: deploy.Deployment, phase: dict) -> list:
    weight = dict(_mix(phase))
    return [dep.queries[n].with_frequency(weight.get(n, 1.0))
            for n in phase["adapt"]]


def prepare(dep: deploy.Deployment, traffic: dict, log=print):
    """Set-up: returns the service, bootstrapped and warm."""
    boot = [dep.queries[n] for n in traffic["bootstrap"]]

    def timed(name, fn):
        t0 = time.perf_counter()
        out = fn()
        log(f"[setup] {name}: {time.perf_counter() - t0:.3f} s")
        return out

    def warm_rounds():
        twin = deploy.service(dep)
        twin.bootstrap(boot)
        for phase in traffic["phases"]:
            twin.drain()
            twin.adapt(round_queries(dep, phase))

    timed("warm_rounds", warm_rounds)
    svc = deploy.service(dep)          # the served one owns the counters
    timed("bootstrap", lambda: svc.bootstrap(boot))
    timed("pre_drift_window", lambda: [svc.serve_window([q]) for q in boot])
    pool = list(dict.fromkeys(n for mix in mixes(traffic) for n, _ in mix))

    def warm_joins():
        for name in pool:
            svc.executor.run_batch([svc.kg.plan(dep.queries[name])], svc.kg)

    timed("warm_joins", warm_joins)
    return svc


def serve(svc, dep: deploy.Deployment, traffic: dict, due_s: np.ndarray,
          names: List[str], seconds: float, log=print) -> Run:
    from jax.profiler import TraceAnnotation

    kg = svc.kg
    phases = traffic["phases"]
    onsets = [k * seconds / len(phases) for k in range(len(phases))]
    queries = [dep.queries[n] for n in names]
    n = len(names)
    done = np.full(n, np.nan)
    epoch = np.full(n, -1, np.int64)
    answers: List[Optional[Tuple[dict, object]]] = [None] * n
    errors: List[str] = []
    plans: Dict[Tuple[str, int], Tuple[tuple, int]] = {}
    layouts = {kg.epoch: kg.triple_shard.copy()}
    rounds: List[Round] = []
    chunk_ms: List[float] = []
    windows: List[Window] = []
    step_errors = 0
    t0 = time.perf_counter()

    def clock() -> float:
        return time.perf_counter() - t0

    def step() -> Optional[float]:
        """One chunk of the pending session; its time, or None if idle."""
        nonlocal step_errors
        if svc.session is None or step_errors:
            return None
        e0 = kg.epoch
        ts = time.perf_counter()
        try:
            with TraceAnnotation("chipbench.step"):
                chunk = svc.step()
        except Exception:                    # the drain stops here
            errors.append(traceback.format_exc())
            log(f"[window] step failed:\n{errors[-1]}")
            step_errors += 1
            chunk = None
        ms = (time.perf_counter() - ts) * 1e3
        chunk_ms.append(ms)
        layouts.setdefault(kg.epoch, kg.triple_shard.copy())
        if chunk is not None:
            rounds[-1].chunks.append((e0, kg.epoch, int(chunk.bytes)))
        return ms

    def adapt(k: int) -> None:
        if rounds and svc.session is not None:
            while step() is not None and svc.session is not None:
                pass
            rounds[-1].forced = True
            rounds[-1].drained_s = clock()
        start = clock()
        pre = kg.epoch
        layouts.setdefault(pre, kg.triple_shard.copy())
        with TraceAnnotation("chipbench.adapt"):
            report = svc.adapt(round_queries(dep, phases[k]))
        ms = (clock() - start) * 1e3
        layouts.setdefault(kg.epoch, kg.triple_shard.copy())
        session = svc.session
        rounds.append(Round(
            onset_s=onsets[k],
            end_s=onsets[k + 1] if k + 1 < len(onsets) else float(seconds),
            pre_epoch=pre, accepted=bool(report.accepted),
            reason=str(report.reason), plan_bytes=int(report.plan.bytes),
            n_chunks=session.n_chunks if session is not None else 0,
            adapt_ms=ms, chunks=[]))
        log(f"[window] round {k} at {start:.3f} s: "
            f"accepted={report.accepted} reason={report.reason} "
            f"moves={report.plan.n_moves} bytes={report.plan.bytes} "
            f"chunks={rounds[-1].n_chunks} in {ms:.1f} ms")

    with TraceAnnotation("chipbench.window"):
        served = 0
        while served < n or len(rounds) < len(phases):
            now = clock()
            if len(rounds) < len(phases) and now >= onsets[len(rounds)]:
                adapt(len(rounds))
                continue
            closed = now >= seconds
            due = n if closed else int(np.searchsorted(due_s, now, "right"))
            if due == served:
                wake = min(due_s[served] if served < n else seconds,
                           onsets[len(rounds)] if len(rounds) < len(phases)
                           else seconds, seconds)
                time.sleep(max(0.0, wake - now))
                continue
            start = now
            step_ms = step()
            if step_ms is not None and not closed:
                due = int(np.searchsorted(due_s, clock(), "right"))
            ts = time.perf_counter()
            misses = 0
            for i in range(served, due):
                try:
                    with TraceAnnotation("chipbench.serve"):
                        results, miss = svc.serve_window([queries[i]])
                except Exception:                # the loop keeps serving
                    errors.append(traceback.format_exc())
                    log(f"[window] serve failed:\n{errors[-1]}")
                    continue
                done[i] = clock()
                epoch[i] = kg.epoch
                answers[i] = results[0]
                misses += len(miss)
                key = (names[i], kg.epoch)
                if key not in plans:
                    p = kg.plan(queries[i])
                    plans[key] = (tuple(op.pattern for op in p.ops),
                                  int(p.ppn))
            windows.append(Window(start, due - served, misses, kg.epoch,
                                  len(rounds) - 1, step_ms,
                                  (time.perf_counter() - ts) * 1e3))
            if step_ms is not None and svc.session is None \
                    and rounds[-1].drained_s is None:
                rounds[-1].drained_s = clock()
            served = due
    return Run(names=list(names), due_s=np.asarray(due_s), done_s=done,
               epoch=epoch, answers=answers, errors=errors, plans=plans,
               layouts=layouts, rounds=rounds, drained=svc.session is None,
               chunk_ms=chunk_ms, windows=windows, seconds=float(seconds),
               step_errors=step_errors)
