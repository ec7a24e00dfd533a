"""Find a drift cell's knee: the highest offered rate at which the queue
keeps up with the drain's passes.

    python3 -m chipbench.sweep --workload <cell> --rates 4 6 8 --seconds 45 --seed 5 [--record]

One process serves the cell's window once per rate, each on a fresh
service over the same deployment. A pass's batch is the requests that
came due while the previous pass ran; while a session drains, a queue
that keeps up holds the batch level, and one that falls behind grows it.
Per rate and round it prints the drain passes' batches and their
least-squares slope in requests per pass (the first pass, which serves
the backlog of the round, left out), and whether the queue emptied in
every drain: each session drained within its phase, and its last three
passes after the first held at most two requests each. The knee is the highest rate at
which the queue emptied, and at every lower rate swept. With
``--record`` the readings, the knee and the cell's rate, 0.8 times the
knee, are written into the cell's traffic file.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

KNEE = ("the highest swept rate at which, as at every lower rate swept, the "
        "queue emptied in every drain: each session drained within its "
        "phase, and its last three drain passes after the first, which "
        "serves the round's backlog, held at most two requests each; the "
        "cell runs at 0.8 x knee")


def slope(values) -> float:
    if len(values) < 2:
        return float("nan")
    x = np.arange(len(values), dtype=np.float64)
    return float(np.polyfit(x, np.asarray(values, np.float64), 1)[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--record", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import arrivals, deploy, registry, stats
    from chipbench import run as runmod

    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    sys.path.insert(0, str(registry.CHECKOUT / "src"))
    devs = runmod.device_or_exit(int(cell["chips"]))
    runmod.compile_cache()
    watch = runmod.CompileWatch()
    cfg = registry.config(cell["config"])
    traffic = registry.traffic(cell["traffic"])
    kind = registry.loop(traffic["kind"])
    dep = deploy.build(cfg, args.seed)
    readings = []
    for rate in sorted(args.rates):
        svc = kind.prepare(dep, traffic, runmod.log)
        due, names = arrivals.schedule(kind.mixes(traffic), rate,
                                       args.seconds)
        watch.on, watch.count, watch.seconds = True, 0, 0.0
        run = kind.serve(svc, dep, traffic, due, names, args.seconds,
                         lambda *_: None)
        watch.on = False
        drains = [[w.n for w in run.windows
                   if w.step_ms is not None and w.round == k]
                  for k in range(len(run.rounds))]
        emptied = all(r.drained_s is not None and not r.forced
                      and max(d[1:][-3:], default=0) <= 2
                      for r, d in zip(run.rounds, drains))
        lat = [float(x) for x in run.latencies_ms if x == x]
        p95 = stats.tail(lat)
        readings.append(dict(
            rate_qps=rate, requests=len(names), drain_batches=drains,
            drained_s=[r.drained_s for r in run.rounds],
            emptied=bool(emptied), p95_ms=p95["value"],
            mean_ms=stats.mean(lat)))
        print(json.dumps(dict(
            readings[-1], drain_slope=[slope(d[1:]) for d in drains],
            passes=len(run.windows), compiles=watch.count,
            compile_s=watch.seconds,
            serve_ms=[round(w.serve_ms, 1) for w in run.windows])),
            flush=True)
        del svc, run
    knee = None
    for r in readings:
        if not r["emptied"]:
            break
        knee = r["rate_qps"]
    print(json.dumps(dict(knee_qps=knee)), flush=True)
    if args.record:
        if knee is None:
            sys.exit("chipbench.sweep: the queue emptied at no rate swept")
        traffic.update(rate_qps=round(0.8 * knee, 3), sweep=dict(
            definition=KNEE,
            device=devs[0].device_kind, seconds=args.seconds,
            seed=args.seed, readings=readings, knee_qps=knee))
        path = registry.HERE / "traffic" / f"{cell['traffic']}.json"
        path.write_text(json.dumps(traffic, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
