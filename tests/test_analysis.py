"""repro.analysis static verifier: PRNG provenance through jaxprs,
donation vs compiled-HLO aliases, recompile hazards, hot-loop purity,
Pallas preflight over zoo shapes, baseline gating, the CLI, and the
`rosa.compile(verify=...)` surface."""

import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import analysis as A
from repro import rosa
from repro.analysis import (AnalysisTarget, Severity, VerificationError,
                            load_baseline, run_checks, write_baseline)
from repro.analysis.findings import AnalysisReport, Finding

F32 = jnp.float32


def _sds(*shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype)


def codes(findings):
    return sorted({f.code for f in findings})


# ---------------------------------------------------------------------------
# PRNG discipline
# ---------------------------------------------------------------------------
class TestPRNG:
    def check(self, fn, *args, **kw):
        t = AnalysisTarget("t", fn, tuple(args), **kw)
        return [f for f in run_checks([t], checks=["prng"])]

    def test_reused_key_flagged(self):
        def f(key, x):
            return x + jax.random.normal(key, x.shape) \
                + jax.random.uniform(key, x.shape)
        fs = self.check(f, _sds(2, dtype=jnp.uint32), _sds(4))
        assert codes(fs) == ["PRNG001"]
        assert all(f.severity == Severity.ERROR for f in fs)

    def test_split_keys_clean(self):
        def f(key, x):
            k1, k2 = jax.random.split(key)
            return x + jax.random.normal(k1, x.shape) \
                + jax.random.uniform(k2, x.shape)
        assert self.check(f, _sds(2, dtype=jnp.uint32), _sds(4)) == []

    def test_fold_in_distinct_consts_clean(self):
        def f(key, x):
            a = jax.random.normal(jax.random.fold_in(key, 0), x.shape)
            b = jax.random.normal(jax.random.fold_in(key, 1), x.shape)
            return x + a + b
        assert self.check(f, _sds(2, dtype=jnp.uint32), _sds(4)) == []

    def test_fold_in_same_const_is_reuse(self):
        # two textually-separate folds of the SAME (key, const) pair are
        # one stream: the memoized derivation must see through them
        def f(key, x):
            a = jax.random.normal(jax.random.fold_in(key, 7), x.shape)
            b = jax.random.uniform(jax.random.fold_in(key, 7), x.shape)
            return x + a + b
        fs = self.check(f, _sds(2, dtype=jnp.uint32), _sds(4))
        assert "PRNG001" in codes(fs)

    def test_constant_baked_key_flagged(self):
        baked = jax.random.PRNGKey(0)

        def f(x):
            return x + jax.random.normal(baked, x.shape)
        fs = self.check(f, _sds(4))
        assert "PRNG002" in codes(fs)

    def test_loop_invariant_key_in_scan_flagged(self):
        def f(key, x):
            def body(c, _):
                return c + jax.random.normal(key, c.shape), None
            return jax.lax.scan(body, x, None, length=4)[0]
        fs = self.check(f, _sds(2, dtype=jnp.uint32), _sds(4))
        assert "PRNG004" in codes(fs)

    def test_per_iteration_fold_in_scan_clean(self):
        def f(key, x):
            def body(c, i):
                k = jax.random.fold_in(key, i)
                return c + jax.random.normal(k, c.shape), None
            return jax.lax.scan(body, x, jnp.arange(4))[0]
        assert self.check(f, _sds(2, dtype=jnp.uint32), _sds(4)) == []


# ---------------------------------------------------------------------------
# Donation
# ---------------------------------------------------------------------------
class TestDonation:
    def test_dropped_donation_flagged(self):
        def f(x, scratch):
            return x * 2.0          # scratch never used -> alias dropped
        t = AnalysisTarget("t", f, (_sds(8, 8), _sds(8, 8)),
                           donate_argnums=(1,))
        fs = run_checks([t], checks=["donation"])
        assert codes(fs) == ["DON001"]
        assert fs.findings[0].severity == Severity.ERROR

    def test_honored_donation_clean(self):
        def f(x, state):
            return state + x
        t = AnalysisTarget("t", f, (_sds(8, 8), _sds(8, 8)),
                           donate_argnums=(1,))
        assert list(run_checks([t], checks=["donation"])) == []

    def test_hot_path_without_donation_warns(self):
        def f(state):
            return state + 1.0
        t = AnalysisTarget("t", f, (_sds(8, 8),), hot_path=True)
        fs = run_checks([t], checks=["donation"])
        assert codes(fs) == ["DON002"]
        assert fs.findings[0].severity == Severity.WARNING


# ---------------------------------------------------------------------------
# Purity
# ---------------------------------------------------------------------------
class TestPurity:
    def test_debug_print_in_scan_body_flagged(self):
        def f(x):
            def body(c, _):
                jax.debug.print("c={c}", c=c[0])
                return c * 2.0, None
            return jax.lax.scan(body, x, None, length=3)[0]
        fs = run_checks([AnalysisTarget("t", f, (_sds(4),))],
                        checks=["purity"])
        assert codes(fs) == ["PUR001"]

    def test_callback_in_hot_path_warns(self):
        def f(x):
            jax.debug.print("tick")
            return x * 2.0
        fs = run_checks(
            [AnalysisTarget("t", f, (_sds(4),), hot_path=True)],
            checks=["purity"])
        assert codes(fs) == ["PUR002"]

    def test_pure_fn_clean(self):
        def f(x):
            return jax.lax.scan(lambda c, _: (c * 2.0, None), x, None,
                                length=3)[0]
        assert list(run_checks([AnalysisTarget("t", f, (_sds(4),))],
                               checks=["purity"])) == []


# ---------------------------------------------------------------------------
# Recompile hazards
# ---------------------------------------------------------------------------
class TestRecompile:
    def test_weak_scalar_warns(self):
        def f(x, s):
            return x * s
        closed_args = (_sds(4), 2.5)    # bare float traces weakly typed
        fs = run_checks([AnalysisTarget("t", f, closed_args)],
                        checks=["recompile"])
        assert "REC001" in codes(fs)

    def test_f64_promotion_warns(self):
        def f(x):
            return x.astype(jnp.float64) if jax.config.jax_enable_x64 \
                else np.float64(1.0) + x
        # without x64 enabled nothing promotes; build the hazard directly
        def g(x):
            return jax.lax.convert_element_type(x, jnp.float64)
        with jax.enable_x64():
            fs = run_checks([AnalysisTarget("t", g, (_sds(4),))],
                            checks=["recompile"])
        assert "REC002" in codes(fs)

    def test_unhashable_static_is_rec003_not_crash(self):
        def f(x, cfg):
            return x * 2.0
        t = AnalysisTarget("t", f, (_sds(4), {"a": 1}), static_argnums=(1,))
        fs = run_checks([t])        # ALL checks: none may CHECKFAIL
        assert codes(fs) == ["REC003"]

    def test_key_typed_args_do_not_crash(self):
        # extended dtypes (key<fry>) must not reach np.dtype()
        def f(key, x):
            return x + jax.random.normal(key, x.shape)
        key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
        fs = run_checks([AnalysisTarget("t", f, (key, _sds(4)))],
                        checks=["recompile"])
        assert "CHECKFAIL" not in codes(fs)


# ---------------------------------------------------------------------------
# Pallas preflight
# ---------------------------------------------------------------------------
class TestPallasPreflight:
    def test_pad_waste_warns(self):
        t = AnalysisTarget("t", gemm_shapes=(("tiny", 3, 5, 7),))
        fs = run_checks([t], checks=["pallas"])
        assert "PAL002" in codes(fs)
        assert all(f.severity <= Severity.WARNING for f in fs)

    def test_aligned_shape_clean(self):
        t = AnalysisTarget("t", gemm_shapes=(("ok", 128, 256, 128),))
        assert list(run_checks([t], checks=["pallas"])) == []

    def test_vmem_blowup_errors(self):
        from repro.kernels.osa_matmul.ops import preflight
        rep = preflight(4096, 4096, 4096, bm=1024, bn=1024, bk=1024)
        assert rep["vmem_bytes"] > 16 * 2**20
        assert not rep["issues"]

    def test_bad_block_param_is_contract_issue(self):
        from repro.kernels.osa_matmul.ops import preflight
        rep = preflight(128, 128, 128, bk=100)
        assert any("bk" in s for s in rep["issues"])

    def test_ssd_lane_dims_are_soft(self):
        t = AnalysisTarget("t", ssd_shapes=(("s", 1, 512, 8, 64, 64),))
        fs = run_checks([t], checks=["pallas"])
        lane = [f for f in fs if f.code == "PAL003"]
        assert lane and all(f.severity == Severity.WARNING for f in lane)


# ---------------------------------------------------------------------------
# HLO parser regression (dtype table + alias map)
# ---------------------------------------------------------------------------
class TestHLOParsing:
    def test_narrow_and_f8_dtypes_accounted(self):
        from repro.analysis.hlo import _shape_list_bytes
        assert _shape_list_bytes("s4[16]") == 8
        assert _shape_list_bytes("u4[16]") == 8
        assert _shape_list_bytes("f8e8m0fnu[32]") == 32
        assert _shape_list_bytes("f8e4m3fn[8], f32[2]") == 16

    def test_unknown_dtype_like_raises(self):
        from repro.analysis.hlo import UnknownDtypeError, _shape_list_bytes
        with pytest.raises(UnknownDtypeError):
            _shape_list_bytes("f8e9xyz[8]")

    def test_non_dtype_tokens_skipped(self):
        from repro.analysis.hlo import _shape_list_bytes
        # sharding annotations etc. must not be mistaken for dtypes
        assert _shape_list_bytes("devices=[2,2]") == 0

    def test_legacy_import_path_still_works(self):
        from repro.launch import hlo_analysis
        assert hlo_analysis.DTYPE_BYTES["s4"] == 0.5
        assert hasattr(hlo_analysis, "analyze")

    def test_alias_parsing_roundtrip(self):
        from repro.analysis.hlo import parse_input_output_aliases
        fn = jax.jit(lambda x, y: (x + y, y * 2.0), donate_argnums=(0, 1))
        txt = fn.lower(jnp.ones((4,)), jnp.ones((4,))).compile().as_text()
        aliases = parse_input_output_aliases(txt)
        assert len(aliases) == 2
        assert sorted(p for p, _ in aliases) == [0, 1]


# ---------------------------------------------------------------------------
# Findings / baseline plumbing
# ---------------------------------------------------------------------------
class TestBaseline:
    def _finding(self, code="X001", sev=Severity.WARNING, loc="here"):
        return Finding(check="x", code=code, severity=sev, subject="s",
                       location=loc, message="m")

    def test_fingerprint_ignores_message(self):
        a = self._finding()
        b = Finding(check="x", code="X001", severity=Severity.WARNING,
                    subject="s", location="here", message="other words")
        assert a.fingerprint == b.fingerprint

    def test_report_json_roundtrip(self):
        rep = AnalysisReport((self._finding(), self._finding("X002")))
        back = AnalysisReport.from_json(rep.to_json())
        assert back == rep

    def test_baseline_gates_only_new(self, tmp_path):
        rep = AnalysisReport((self._finding("X001"), self._finding("X002")))
        path = tmp_path / "base.json"
        write_baseline(path, AnalysisReport((self._finding("X001"),)))
        new = rep.new_against(load_baseline(path))
        assert [f.code for f in new] == ["X002"]

    def test_info_never_gates(self, tmp_path):
        rep = AnalysisReport((self._finding(sev=Severity.INFO),))
        assert rep.new_against(set()) == ()

    def test_missing_baseline_is_empty(self, tmp_path):
        assert load_baseline(tmp_path / "nope.json") == set()

    def test_wrong_schema_raises(self, tmp_path):
        p = tmp_path / "bad.json"
        p.write_text(json.dumps({"schema": 99, "findings": {}}))
        with pytest.raises(ValueError):
            load_baseline(p)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
class TestCLI:
    def test_zoo_scan_baseline_cycle(self, tmp_path, capsys):
        from repro.analysis.cli import main
        base = str(tmp_path / "baseline.json")
        rep_json = str(tmp_path / "report.json")
        argv = ["--no-models", "--no-serve", "--baseline", base]
        # cold: zoo shapes produce findings, none acknowledged -> exit 1
        assert main(argv) == 1
        assert main(argv + ["--write-baseline"]) == 0
        # acknowledged -> exit 0, bench-schema report written
        assert main(argv + ["--json", rep_json]) == 0
        doc = json.loads((tmp_path / "report.json").read_text())
        res = doc["results"][0]
        assert res["name"] == "static_analysis"
        metrics = {m["name"]: m for m in res["metrics"]}
        assert metrics["findings_new"]["value"] == 0
        assert metrics["findings_new"]["gate"] is True
        assert metrics["findings_total"]["value"] > 0

    def test_checks_subset_validated(self):
        with pytest.raises(ValueError):
            run_checks([], checks=["nonexistent"])


# ---------------------------------------------------------------------------
# rosa.compile(verify=...)
# ---------------------------------------------------------------------------
class TestCompileVerify:
    @pytest.fixture()
    def engine(self):
        return rosa.Engine(plan=rosa.ExecutionPlan(default=rosa.RosaConfig()))

    def _bad(self, engine, x, scratch):
        k = engine.key
        a = jax.random.normal(k, x.shape)
        b = jax.random.uniform(k, x.shape)     # reuse
        return x + a + b                       # scratch donated, unused

    def test_error_mode_rejects_reuse_and_dropped_donation(self, engine):
        x = _sds(8, 8)
        with pytest.raises(VerificationError) as ei:
            rosa.compile(self._bad, engine, (x, x), donate_argnums=(1,),
                         cache=False, verify="error")
        got = codes(ei.value.report.findings)
        assert "PRNG001" in got and "DON001" in got

    def test_warn_mode_warns_but_builds(self, engine):
        x = _sds(8, 8)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            p = rosa.compile(self._bad, engine, (x, x), donate_argnums=(1,),
                             cache=False, verify="warn")
        assert isinstance(p, rosa.Program)
        assert any("PRNG001" in str(x.message) for x in w)

    def test_clean_program_passes_error_mode(self, engine):
        def good(eng, x):
            return eng.matmul(x, x, name="l0")
        p = rosa.compile(good, engine, (_sds(8, 8),), cache=False,
                         verify="error")
        assert isinstance(p, rosa.Program)

    def test_invalid_mode_rejected(self, engine):
        with pytest.raises(ValueError):
            rosa.compile(lambda e, x: x, engine, (_sds(4),), cache=False,
                         verify="loud")

    def test_verify_program_helper(self, engine):
        def good(eng, x):
            return eng.matmul(x, x, name="l0")
        p = rosa.compile(good, engine, (_sds(8, 8),), cache=False)
        rep = A.verify_program(p, (_sds(8, 8),))
        assert rep.errors == ()
