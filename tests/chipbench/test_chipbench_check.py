"""The comparison that decides ``correct``, driven through the harness's
own set-up and window at LUBM(1) on the CPU (the harness's look for a chip
skipped): the program comes out correct, the control does not, one
corrupted binding is caught, and each fault the cell can have, planted
underneath the timed path, turns ``correct`` false."""
import json
import shutil

import numpy as np
import pytest

from chipbench import arrivals, check, deploy, registry
from chipbench.reference import Reference

CELL = "lubm10-exp1-drift"
SECONDS = 3.0
SEED = 2 ** 31 + 17


@pytest.fixture(scope="module")
def small_root(tmp_path_factory):
    """The benchmark's files with the cell's configuration cut to LUBM(1)."""
    root = tmp_path_factory.mktemp("chipbench")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(registry.HERE / sub, root / sub)
    bench = registry.benchmark()
    cell = registry.cell(bench, CELL)
    path = root / "configs" / f"{cell['config']}.json"
    cfg = json.loads(path.read_text())
    cfg["dataset"]["scale"] = 1
    path.write_text(json.dumps(cfg))
    return root, bench, cell


def run_small(small_root, seed):
    """A whole run of the cell through ``run.run_cell`` on the CPU: the
    harness's look for a chip is the only step left out."""
    import jax
    from chipbench import run as runmod

    root, bench, _ = small_root
    return runmod.run_cell(bench, CELL, seed, SECONDS, False, jax.devices(),
                           runmod.CompileWatch(), root)


def serve(small_root, seed=SEED):
    root, bench, cell = small_root
    cfg = registry.config(cell["config"], root)
    traffic = registry.traffic(cell["traffic"], root)
    kind = registry.loop(traffic["kind"])
    dep = deploy.build(cfg, seed)
    svc = kind.prepare(dep, traffic, log=lambda *_: None)
    due, names = arrivals.schedule(kind.mixes(traffic),
                                   float(traffic["rate_qps"]), SECONDS)
    run = kind.serve(svc, dep, traffic, due, names, SECONDS,
                     log=lambda *_: None)
    ref = Reference(dep.triples, int(cfg["shards"]))
    patterns = {n: q.patterns for n, q in dep.queries.items()}
    return run, ref, patterns


def numbers(run, ref, patterns, digests=None):
    digests = check.program_digests(run) if digests is None else digests
    return check.compare(run, ref, patterns, True, digests)


@pytest.fixture(scope="module")
def served(small_root):
    return serve(small_root)


def test_program_is_correct(served):
    run, ref, patterns = served
    got = numbers(run, ref, patterns)
    assert check.verdict(got), got
    assert len(run.rounds) == len(registry.traffic(
        registry.cell(registry.benchmark(), CELL)["traffic"])["phases"])
    assert all(r.accepted and r.chunks for r in run.rounds)
    assert len(run.plans) > 0
    assert not np.isnan(run.done_s).any()


def test_control_is_not_correct(served):
    run, ref, patterns = served
    got = check.compare(run, ref, patterns, True,
                        check.control_digests(run, ref, patterns))
    assert got["stats_wrong"] > 0 and not check.verdict(got)


def test_one_corrupted_binding_is_caught(served):
    run, ref, patterns = served
    digests = check.program_digests(run)
    i = next(i for i, (b, st) in enumerate(run.answers) if st.rows > 0)
    bindings, stats = run.answers[i]
    bad = {v: c.copy() for v, c in bindings.items()}
    var = sorted(bad)[0]
    bad[var][0] += 1
    digests[i] = check.digest(bad, stats)
    got = numbers(run, ref, patterns, digests)
    assert got["answers_wrong"] == 1 and not check.verdict(got)


def test_stale_layout_counts_are_caught(served):
    run, ref, patterns = served
    digests = check.program_digests(run)
    i = next(i for i, d in enumerate(digests) if d[3]["rows_shipped"] > 0)
    keys, hashes, rows, st = digests[i]
    digests[i] = (keys, hashes, rows, dict(st, rows_shipped=0))
    assert numbers(run, ref, patterns, digests)["stats_wrong"] == 1


def _step_unchanged(monkeypatch):
    from repro.api import facade
    monkeypatch.setattr(facade.PartitionedKG, "apply_chunk",
                        lambda self, chunk: None)


def _half_batch(monkeypatch):
    from repro.query import exec as qexec
    orig = qexec.JaxExecutor.run_batch

    def half(self, plans, kg):
        # every other plan, the first among them, is left out
        kept = iter(orig(self, list(plans[1::2]), kg))
        return [next(kept) if i % 2 else ({}, qexec.ExecStats())
                for i in range(len(plans))]
    monkeypatch.setattr(qexec.JaxExecutor, "run_batch", half)


def _answer_altered(monkeypatch):
    from repro.query import exec as qexec
    orig = qexec._join_jax

    def altered(*args, **kwargs):
        out = orig(*args, **kwargs)
        if out and len(next(iter(out.values()))):
            var = sorted(out)[0]
            out = dict(out)
            out[var] = out[var].copy()
            out[var][0] += 1
        return out
    monkeypatch.setattr(qexec, "_join_jax", altered)


@pytest.mark.parametrize("fault", [_step_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["step_unchanged", "half_batch",
                              "answer_altered"])
def test_fault_underneath_makes_run_incorrect(small_root, monkeypatch,
                                              fault):
    # set-up stays sound; the fault is planted as the window opens
    kind = registry.loop("drift")
    window = kind.serve

    def faulty(*args, **kwargs):
        fault(monkeypatch)
        return window(*args, **kwargs)
    monkeypatch.setattr(kind, "serve", faulty)
    result = run_small(small_root, SEED + 1)
    assert result["correct"] is False, result["checks"]
    assert list(result)[-1] == "checks"


def test_whole_run_is_correct(small_root):
    result = run_small(small_root, SEED + 2)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "query_p95_ms",
                                      "query_mean_ms"}
