"""Readings that set the limits of ``correct``: the program's fault counts
and the control's, over several seeds in one process.

    python3 -m chipbench.control --workload <cell> --seeds 11 12 13 --seconds 45

For each seed it builds the deployment, serves the cell's window as a run
does, and compares what the program served (``check.compare``). It then
puts the control in the program's place: the reference with results
cached per query and not per layout epoch (``check.control_digests``).
One JSON line per seed; the control has to come out not correct.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys


def readings(bench: dict, cell_name: str, seed: int, seconds: float,
             log, root=None) -> dict:
    from chipbench import arrivals, check, deploy, registry
    from chipbench.reference import Reference

    root = root or registry.HERE
    cell = registry.cell(bench, cell_name)
    cfg = registry.config(cell["config"], root)
    traffic = registry.traffic(cell["traffic"], root)
    kind = registry.loop(traffic["kind"])
    dep = deploy.build(cfg, seed)
    svc = kind.prepare(dep, traffic, log)
    due, names = arrivals.schedule(kind.mixes(traffic),
                                   float(traffic["rate_qps"]), seconds)
    run = kind.serve(svc, dep, traffic, due, names, seconds, log)
    digests = check.program_digests(run)
    run.answers = [None] * len(run.answers)
    del svc
    dep.store = None
    gc.collect()
    ref = Reference(dep.triples, int(cfg["shards"]))
    patterns = {n: q.patterns for n, q in dep.queries.items()}
    expect = bool(cfg["guarantees"]["round_accepted"])
    program = check.compare(run, ref, patterns, expect, digests)
    control = check.compare(run, ref, patterns, expect,
                            check.control_digests(run, ref, patterns))
    return dict(seed=seed, requests=len(names),
                chunks=[len(r.chunks) for r in run.rounds],
                drained=run.drained, program=program,
                program_correct=check.verdict(program), control=control,
                control_correct=check.verdict(control))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import registry
    from chipbench import run as runmod

    bench = registry.benchmark()
    sys.path.insert(0, str(registry.CHECKOUT / "src"))
    runmod.device_or_exit(int(registry.cell(bench, args.workload)["chips"]))
    runmod.compile_cache()
    for seed in args.seeds:
        out = readings(bench, args.workload, seed, args.seconds,
                       runmod.log)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
