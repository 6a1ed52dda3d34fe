"""Benchmark harness: BENCH_<n>.json schema, regression gate, DSE v2 parity."""

import copy

import pytest

from repro.bench import compare as BC
from repro.bench import schema as BS
from repro.configs.model_zoo import layers_from_config, zoo_workloads
from repro.configs.paper_cnns import WORKLOADS
from repro.core import dse


def _report(**kw):
    base = dict(
        bench_seq=0, mode="quick", created_utc="2026-07-30T00:00:00Z",
        env={"python": "3.10", "jax": "0.4.37"},
        results=[BS.BenchResult(
            name="fig7", status="ok", wall_s=1.0,
            metrics=[
                BS.Metric("best_config", "R=8,C=8,T=16", gate=True),
                BS.Metric("reduction_vs_deap", 0.34, unit="frac", gate=True,
                          rel_tol=0.05, direction="higher_is_better"),
                BS.Metric("edp", 2.0e-5, unit="J*s", gate=True,
                          rel_tol=0.05, direction="lower_is_better"),
                BS.Metric("wall_s", 1.23),          # ungated
            ])])
    base.update(kw)
    return BS.BenchReport(**base)


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------
def test_schema_roundtrip(tmp_path):
    rep = _report()
    path = BS.save(rep, tmp_path / "BENCH_0.json")
    back = BS.load(path)
    assert back == rep


def test_schema_validate_rejects_bad_docs():
    good = _report().to_dict()
    for mutate in (
        lambda d: d.update(schema_version=99),
        lambda d: d.update(mode="fastest"),
        lambda d: d.update(bench_seq=-1),
        lambda d: d["results"][0].update(status="exploded"),
        lambda d: d["results"][0].update(status="failed", error=""),
        lambda d: d["results"][0]["metrics"][0].update(direction="sideways"),
        lambda d: d["results"].append(copy.deepcopy(d["results"][0])),
        lambda d: d["results"][0].update(metrics={"name": "x"}),
        lambda d: d["results"][0].update(metrics=["not-an-object"]),
    ):
        doc = copy.deepcopy(good)
        mutate(doc)
        with pytest.raises(BS.SchemaError):
            BS.validate(doc)


def test_schema_omitted_rel_tol_means_exact():
    """A hand-edited metric without rel_tol must not inherit a tolerance."""
    doc = _report().to_dict()
    del doc["results"][0]["metrics"][1]["rel_tol"]
    rep = BS.from_dict(doc)
    assert rep.results[0].metric("reduction_vs_deap").rel_tol == 0.0


def test_next_bench_path_sequencing(tmp_path):
    assert BS.next_bench_path(tmp_path).name == "BENCH_2.json"
    (tmp_path / "BENCH_4.json").write_text("{}")
    assert BS.next_bench_path(tmp_path).name == "BENCH_5.json"
    assert BS.next_bench_path(tmp_path, seq=7).name == "BENCH_7.json"


# ---------------------------------------------------------------------------
# Compare gate
# ---------------------------------------------------------------------------
def test_compare_identical_passes():
    res = BC.compare(_report(), _report())
    assert res.ok and not res.regressions


def test_compare_within_tolerance_passes():
    cur = _report()
    cur.results[0].metric("reduction_vs_deap").value = 0.335  # -1.5% < 5%
    cur.results[0].metric("edp").value = 2.05e-5              # +2.5% < 5%
    assert BC.compare(_report(), cur).ok


def test_compare_regression_fails_per_direction():
    # lower_is_better metric grows past tol -> regression
    cur = _report()
    cur.results[0].metric("edp").value = 2.2e-5               # +10%
    res = BC.compare(_report(), cur)
    assert not res.ok
    assert [v.key for v in res.regressions] == ["fig7.edp"]
    # ...but an *improvement* of the same size is fine
    cur.results[0].metric("edp").value = 1.8e-5
    assert BC.compare(_report(), cur).ok
    # higher_is_better metric shrinking past tol -> regression
    cur = _report()
    cur.results[0].metric("reduction_vs_deap").value = 0.30   # -12%
    assert not BC.compare(_report(), cur).ok


def test_compare_string_and_missing_metrics():
    cur = _report()
    cur.results[0].metric("best_config").value = "R=4,C=4,T=64"
    res = BC.compare(_report(), cur)
    assert [v.key for v in res.regressions] == ["fig7.best_config"]

    cur = _report()
    cur.results[0].metrics = [m for m in cur.results[0].metrics
                              if m.name != "edp"]
    res = BC.compare(_report(), cur)
    assert not res.ok and res.regressions[0].note.startswith("gated metric")


def test_compare_failed_bench_fails_gate():
    cur = _report()
    cur.results.append(BS.BenchResult(name="table4", status="failed",
                                      wall_s=0.1, error="boom"))
    res = BC.compare(_report(), cur)
    assert not res.ok and res.failed_benches == ["table4"]


def test_compare_mode_mismatch_fails_loudly():
    """quick vs full runs gate different scopes -> explicit failure, not a
    pile of spurious metric regressions."""
    cur = _report(mode="full")
    res = BC.compare(_report(), cur)
    assert not res.ok and "quick" in res.mode_mismatch
    assert not res.verdicts          # no misleading per-metric verdicts
    assert "MODE MISMATCH" in BC.format_result(res)


def test_compare_tol_scale():
    cur = _report()
    cur.results[0].metric("edp").value = 2.2e-5               # +10% > 5%
    assert not BC.compare(_report(), cur).ok
    assert BC.compare(_report(), cur, tol_scale=3.0).ok       # 15% tol


# ---------------------------------------------------------------------------
# Runner failure propagation
# ---------------------------------------------------------------------------
def test_runner_records_failures_and_continues(monkeypatch, capsys):
    """A bench that raises is recorded as failed; the others still run and
    the runner exits non-zero at the END (the old aggregator aborted)."""
    from benchmarks import run as R

    calls = []

    def ok_bench(quick):
        calls.append("ok")
        return [BS.Metric("x", 1.0)]

    def bad_bench(quick):
        calls.append("bad")
        raise RuntimeError("boom")

    def skip_bench(quick):
        raise R.SkipBench("no inputs")

    monkeypatch.setattr(R, "BENCHES", {"bad": bad_bench, "ok": ok_bench,
                                       "skip": skip_bench})
    # main() would point this worker's compilation cache at the checkout
    monkeypatch.setattr(R, "enable_compile_cache", lambda: None)
    results = R.run_benches(["bad", "ok", "skip"], quick=True)
    assert calls == ["bad", "ok"]          # ok still ran after the failure
    by = {r.name: r for r in results}
    assert by["bad"].status == "failed" and "boom" in by["bad"].error
    assert by["ok"].status == "ok"
    assert by["skip"].status == "skipped" and "no inputs" in by["skip"].error
    rc = R.main(["--quick"])
    assert rc == 1                         # registry still patched -> fails


# ---------------------------------------------------------------------------
# DSE v2: vmapped engine vs scalar reference
# ---------------------------------------------------------------------------
def test_dse_vmap_matches_scalar_reference():
    """ISSUE 2 acceptance: <=1e-6 relative on the full default grid."""
    wls = [dse.Workload(n, ls) for n, ls in WORKLOADS.items()]
    pts_v = dse.sweep(wls, engine="vmap", batch=8)
    pts_s = dse.sweep(wls, engine="scalar", batch=8)
    assert [p.label for p in pts_v] == [p.label for p in pts_s]
    by_label = {p.label: p for p in pts_s}
    for pv in pts_v:
        ps = by_label[pv.label]
        for attr in ("metric", "geomean", "worst"):
            a, b = getattr(pv, attr), getattr(ps, attr)
            assert abs(a - b) <= 1e-6 * abs(b), (pv.label, attr)
        for name in pv.rel_edp:
            a, b = pv.rel_edp[name], ps.rel_edp[name]
            assert abs(a - b) <= 1e-6 * abs(b), (pv.label, name)


def test_dse_unknown_engine_rejected():
    wls = [dse.Workload("alexnet", WORKLOADS["alexnet"])]
    with pytest.raises(ValueError):
        dse.sweep(wls, engine="quantum")


# ---------------------------------------------------------------------------
# Model zoo
# ---------------------------------------------------------------------------
def test_zoo_covers_all_registry_archs():
    from repro.configs import ARCHS, get_config
    for name in ARCHS:
        layers = layers_from_config(get_config(name), seq_len=128)
        assert layers, name
        assert all(l.m > 0 and l.k > 0 and l.n > 0 for l in layers), name
        assert layers[-1].name == "lm_head"
        assert len({l.name for l in layers}) == len(layers), \
            f"{name}: duplicate layer names"


def test_zoo_sweep_single_jitted_call():
    """Grid x zoo cross-product evaluates through the vmapped engine."""
    wls = zoo_workloads(seq_len=128, include_paper=False,
                        archs=["qwen3-32b", "mamba2-1.3b"])
    pts = dse.evaluate_grid(wls, dse.default_candidates(), batch=2)
    assert len(pts) == len(dse.default_candidates())
    for p in pts:
        assert set(p.rel_edp) == {"qwen3-32b", "mamba2-1.3b"}
        assert p.metric > 0
