"""flexlint: per-rule fixtures proving each checker catches a seeded
violation and honors suppressions, registry consistency, and the
repo-clean meta-test (the same invariant the CI gate enforces).
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

from flexflow_tpu.analysis import (
    ClockRule,
    Context,
    FaultSiteRule,
    JitRule,
    LockRule,
    MetricNameRule,
    SourceFile,
    analyze_repo,
    analyze_source,
    emit_site_table,
    parse_registry,
    run_rules,
)
from flexflow_tpu.runtime import faults

pytestmark = pytest.mark.analysis

ROOT = Path(__file__).resolve().parent.parent


def findings(src, rule, relpath="flexflow_tpu/example.py"):
    report = analyze_source(src, relpath=relpath, rule_names=[rule])
    return report.findings


# --------------------------------------------------------------- clocks
class TestClockRule:
    def test_flags_direct_wall_clock(self):
        src = "import time\n\ndef f():\n    return time.monotonic()\n"
        out = findings(src, "clock-discipline")
        assert len(out) == 1 and "time.monotonic" in out[0].message

    def test_flags_from_import_alias(self):
        src = "from time import perf_counter as pc\n\ndef f():\n    return pc()\n"
        out = findings(src, "clock-discipline")
        assert len(out) == 1 and "perf_counter" in out[0].message

    def test_injectable_default_reference_is_allowed(self):
        src = (
            "import time\n\n"
            "def mk(clock=time.monotonic):\n    return clock()\n"
        )
        assert findings(src, "clock-discipline") == []

    def test_whitelist_file(self):
        src = "import time\n\ndef f():\n    return time.perf_counter()\n"
        assert findings(src, "clock-discipline", relpath="tools/chaoscheck.py") == []
        # the scheduler whitelist covers perf_counter ONLY (PR 6 dual-stamp)
        assert findings(
            src, "clock-discipline",
            relpath="flexflow_tpu/generation/scheduler.py",
        ) == []
        wall = "import time\n\ndef f():\n    return time.time()\n"
        assert len(findings(
            wall, "clock-discipline",
            relpath="flexflow_tpu/generation/scheduler.py",
        )) == 1
        # the engine opens its spans through obs/steptrace.phase and
        # reads no clock of its own any more (ISSUE 23)
        assert len(findings(
            src, "clock-discipline",
            relpath="flexflow_tpu/generation/engine.py",
        )) == 1

    def test_module_alias_does_not_evade(self):
        src = "import time as t\n\ndef f():\n    return t.monotonic()\n"
        out = findings(src, "clock-discipline")
        assert len(out) == 1 and "time.monotonic" in out[0].message

    def test_suppression(self):
        src = (
            "import time\n\ndef f():\n"
            "    return time.time()  # flexlint: disable=clock-discipline\n"
        )
        report = analyze_source(src, rule_names=["clock-discipline"])
        assert report.findings == [] and len(report.suppressed) == 1

    def test_strict_path_flags_every_reference(self):
        # under flexflow_tpu/sim/ the rule is strict: the import, the
        # injectable-default reference, AND the calls are all findings,
        # perf_counter included, whitelist ignored
        src = (
            "import time\n"
            "from time import perf_counter as pc\n\n"
            "def mk(clock=time.monotonic):\n"
            "    return clock() + pc() + time.time()\n"
        )
        out = findings(src, "clock-discipline",
                       relpath="flexflow_tpu/sim/example.py")
        assert len(out) == 4
        assert all("strict virtual-time" in f.message for f in out)
        flagged = {m for f in out for m in
                   ("perf_counter", "monotonic", "time.time")
                   if m in f.message}
        assert flagged == {"perf_counter", "monotonic", "time.time"}
        # the same source outside the strict path: only the two calls
        # (the default-argument reference stays the injectable idiom)
        assert len(findings(src, "clock-discipline")) == 2

    def test_strict_path_ignores_whitelist_shape(self):
        # even a perf_counter-only usage — whitelisted for the engine
        # under the PR 6 dual-stamp decision — is a violation in the sim
        src = "import time\n\ndef f():\n    return time.perf_counter()\n"
        out = findings(src, "clock-discipline",
                       relpath="flexflow_tpu/sim/costs.py")
        assert len(out) == 1 and "perf_counter" in out[0].message

    def test_suppression_with_hyphen_separated_reason(self):
        src = (
            "import time\n\ndef f():\n"
            "    return time.time()  "
            "# flexlint: disable=clock-discipline - bounded real wait\n"
        )
        report = analyze_source(src, rule_names=["clock-discipline"])
        assert report.findings == [] and len(report.suppressed) == 1


# ---------------------------------------------------------------- locks
LOCKED_CLASS = """import threading

class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0  # guarded-by: _lock

    def bump(self):
        {bump_body}

    def read_locked(self):
        return self.n  # called with the lock held, by convention

    def snapshot(self):
        with self._lock:
            return self.n
"""


class TestLockRule:
    def test_flags_unlocked_access(self):
        src = LOCKED_CLASS.format(bump_body="self.n += 1")
        out = findings(src, "lock-discipline")
        assert len(out) == 1
        assert "Counter.n" in out[0].message and "with self._lock" in out[0].message

    def test_locked_access_and_locked_suffix_pass(self):
        src = LOCKED_CLASS.format(
            bump_body="with self._lock:\n            self.n += 1"
        )
        assert findings(src, "lock-discipline") == []

    def test_lambda_inside_with_is_still_deferred(self):
        # the PR 5 gauge-dict shape: the lambda BODY runs later, on a
        # scrape thread, with no lock held — lexical nesting inside the
        # with block must not exempt it
        src = """import threading

class Stats:
    def __init__(self):
        self._lock = threading.Lock()
        self.v = 0  # guarded-by: _lock

    def register(self, add_gauge):
        with self._lock:
            add_gauge("v", lambda: self.v)
"""
        out = findings(src, "lock-discipline")
        assert len(out) == 1 and "Stats.v" in out[0].message

    def test_suppression(self):
        src = LOCKED_CLASS.format(
            bump_body="self.n += 1  # flexlint: disable=lock-discipline"
        )
        report = analyze_source(src, rule_names=["lock-discipline"])
        assert report.findings == [] and len(report.suppressed) == 1

    def test_later_with_item_runs_under_earlier_lock(self):
        # `with self._lock, f(self.n):` evaluates left-to-right — the
        # second item already holds the lock
        src = """import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.n = 0  # guarded-by: _lock

    def f(self, opener):
        with self._lock, opener(self.n):
            return self.n
"""
        assert findings(src, "lock-discipline") == []

    def test_guard_marker_after_prose_registers(self):
        # "# ring is bounded; guarded-by: _lock" must register — a
        # prose prefix silently disabling the annotation masked four
        # real Fleet._pending findings
        src = """import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.q = []  # requests awaiting a replica; guarded-by: _lock

    def depth(self):
        return len(self.q)
"""
        out = findings(src, "lock-discipline")
        assert len(out) == 1 and "C.q" in out[0].message

    def test_reentrant_relock_keeps_outer_hold(self):
        # Fleet's RLock shape: an inner `with self._lock:` exiting must
        # not count as releasing the outer hold
        src = """import threading

class C:
    def __init__(self):
        self._lock = threading.RLock()
        self.n = 0  # guarded-by: _lock

    def f(self):
        with self._lock:
            with self._lock:
                self.n += 1
            return self.n
"""
        assert findings(src, "lock-discipline") == []

    def test_trailing_comment_does_not_leak_to_next_line(self):
        src = """import threading

class C:
    def __init__(self):
        self._lock = threading.Lock()
        self.a = 0  # guarded-by: _lock
        self.b = 0

    def f(self):
        return self.b
"""
        assert findings(src, "lock-discipline") == []


# ------------------------------------------------------------------ jit
JIT_FN = """def decode(params, tokens, reg):
    reg.note_trace("decode", {{}})
    {body}
"""


class TestJitRule:
    @pytest.mark.parametrize("body,needle", [
        ("return tokens.item()", ".item()"),
        ("return int(tokens)", "int()"),
        ("return np.asarray(tokens)", "np.asarray"),
        ("if tokens > 0:\n        return 1\n    return 0", "Python `if`"),
        ("for t in tokens:\n        pass", "iteration"),
    ])
    def test_flags_host_constructs(self, body, needle):
        out = findings(JIT_FN.format(body=body), "jit-discipline")
        assert out and needle in out[0].message

    def test_static_shape_branch_is_allowed(self):
        body = "s = tokens.shape[1]\n    if s > 8:\n        return s\n    return 0"
        assert findings(JIT_FN.format(body=body), "jit-discipline") == []

    def test_non_jit_function_not_scanned(self):
        src = "def host(tokens):\n    return tokens.item()\n"
        assert findings(src, "jit-discipline") == []

    def test_instrument_registration_marks_function(self):
        src = (
            "def step(x):\n    return int(x)\n\n"
            "compiled = jit(REG.instrument('step', step))\n"
        )
        out = findings(src, "jit-discipline")
        assert len(out) == 1 and "int()" in out[0].message

    def test_posonly_and_vararg_params_are_tainted(self):
        src = (
            "def decode(tokens, /, *rest, reg):\n"
            '    reg.note_trace("decode", {})\n'
            "    out = 0\n"
            "    if tokens.sum() > 0:\n"
            "        for r in rest:\n"
            "            out += float(r)\n"
            "    return out\n"
        )
        out = findings(src, "jit-discipline")
        # the if on a posonly param, iteration over *rest, and float()
        # on the tainted loop target
        assert len(out) == 3

    def test_suppression(self):
        body = "return tokens.item()  # flexlint: disable=jit-discipline"
        report = analyze_source(JIT_FN.format(body=body),
                                rule_names=["jit-discipline"])
        assert report.findings == [] and len(report.suppressed) == 1


# ---------------------------------------------------------- fault sites
def site_ctx(src=None, readme=None, relpath="flexflow_tpu/generation/x.py"):
    files = [] if src is None else [SourceFile(relpath, src)]
    ctx = Context(root=ROOT, files=files)
    if readme is not None:
        ctx.readme_text = readme
    return ctx


class TestFaultSiteRule:
    def test_typod_inject_site_is_caught(self):
        src = 'from ..runtime import faults\nfaults.inject("generation.decode_stpe")\n'
        report = run_rules([FaultSiteRule()], site_ctx(src))
        msgs = [f.message for f in report.findings
                if "generation/x.py" in f.path]
        assert len(msgs) == 1 and "unregistered site" in msgs[0]

    def test_registered_literal_still_asks_for_constant(self):
        src = 'from ..runtime import faults\nfaults.inject("generation.prefill")\n'
        report = run_rules([FaultSiteRule()], site_ctx(src))
        msgs = [f.message for f in report.findings
                if "generation/x.py" in f.path]
        assert len(msgs) == 1 and "registry constant" in msgs[0]

    def test_constant_reference_is_clean(self):
        src = (
            "from ..runtime import faults\n"
            "faults.inject(faults.GENERATION_PREFILL)\n"
        )
        report = run_rules([FaultSiteRule()], site_ctx(src))
        assert [f for f in report.findings if "generation/x.py" in f.path] == []

    def test_unknown_constant_is_caught(self):
        src = (
            "from ..runtime import faults\n"
            "faults.inject(faults.GENERATION_DECODE_STPE)\n"
        )
        report = run_rules([FaultSiteRule()], site_ctx(src))
        msgs = [f.message for f in report.findings
                if "generation/x.py" in f.path]
        assert len(msgs) == 1 and "unknown registry constant" in msgs[0]

    def test_plan_on_typo_is_caught(self):
        src = 'plan.on("generation.decode_stpe", mode="error")\n'
        report = run_rules([FaultSiteRule()],
                           site_ctx(src, relpath="tools/mychaos.py"))
        msgs = [f.message for f in report.findings if "mychaos" in f.path]
        assert len(msgs) == 1 and "typo" in msgs[0]

    def test_readme_drift_is_caught(self):
        readme = (ROOT / "README.md").read_text(encoding="utf-8")
        edited = readme.replace("| `generation.decode_step` |",
                                "| `generation.decode_stpe` |")
        assert edited != readme
        report = run_rules([FaultSiteRule()], site_ctx(readme=edited))
        msgs = [f.message for f in report.findings if f.path == "README.md"]
        assert any("missing registered site" in m for m in msgs)
        assert any("unregistered site" in m for m in msgs)

    def test_registry_matches_module_and_table_roundtrip(self):
        constants, sites, err = parse_registry(
            (ROOT / "flexflow_tpu/runtime/faults.py").read_text(encoding="utf-8")
        )
        assert err is None
        # the parsed registry IS the imported registry
        assert sites == dict(faults.SITES)
        assert set(constants.values()) == set(faults.SITES)
        # and the checked-in README embeds exactly the generated table
        table = emit_site_table(sites)
        assert table in (ROOT / "README.md").read_text(encoding="utf-8")


# --------------------------------------------------------- metric names
class TestMetricNameRule:
    def run_with(self, prom=None, golden=None):
        ctx = Context(root=ROOT, files=[])
        if prom is not None:
            ctx.prom_source = prom
        if golden is not None:
            ctx.golden_text = golden
        return run_rules([MetricNameRule()], ctx)

    def test_unpinned_family_is_caught(self):
        prom = 'FAMILY = "flexflow_serving_requets_total"\n'  # typo
        report = self.run_with(prom=prom)
        assert any("not pinned in the golden" in f.message
                   for f in report.findings)

    def test_counter_must_end_total(self):
        golden = "# TYPE flexflow_serving_failovers counter\n"
        report = self.run_with(prom="", golden=golden)
        assert any("must end in _total" in f.message for f in report.findings)

    def test_bad_label_name_is_caught(self):
        golden = (
            "# TYPE flexflow_serving_requests_total counter\n"
            'flexflow_serving_requests_total{Model="m"} 1\n'
        )
        report = self.run_with(prom="", golden=golden)
        assert any("label name 'Model'" in f.message for f in report.findings)

    def test_current_prom_and_golden_are_clean(self):
        assert self.run_with().findings == []


# ------------------------------------------------------------ meta-test
class TestRepoClean:
    def test_repo_has_zero_unsuppressed_findings(self):
        """The CI invariant: `python tools/flexlint.py` exits 0 — no
        unsuppressed, un-baselined findings anywhere in the repo."""
        report = analyze_repo(ROOT)
        assert report.findings == [], "\n" + "\n".join(
            f.render() for f in report.findings
        )

    def test_baseline_is_empty_by_policy(self):
        data = json.loads(
            (ROOT / "tools/flexlint_baseline.json").read_text(encoding="utf-8")
        )
        assert data["findings"] == [], (
            "intentional exemptions belong inline as "
            "`# flexlint: disable=<rule> — reason`, not in the baseline"
        )

    def test_cli_exit_codes_and_report(self, tmp_path):
        out = tmp_path / "report.json"
        proc = subprocess.run(
            [sys.executable, str(ROOT / "tools/flexlint.py"),
             "--json", str(out)],
            capture_output=True, text=True, cwd=str(ROOT), timeout=300,
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(out.read_text(encoding="utf-8"))
        assert report["counts"]["findings"] == 0
        assert report["files_scanned"] > 50

    def test_update_baseline_preserves_grandfathered_entries(self, tmp_path):
        """--update-baseline must keep still-firing grandfathered
        findings (and entries of rules outside a --rules scope), not
        drop them for the current actionable set only."""
        bad = tmp_path / "flexflow_tpu" / "bad.py"
        bad.parent.mkdir(parents=True)
        bad.write_text("import time\n\ndef f():\n    return time.time()\n")
        baseline = tmp_path / "baseline.json"
        cli = [sys.executable, str(ROOT / "tools/flexlint.py"),
               "--root", str(tmp_path), "--baseline", str(baseline)]
        # grandfather the clock finding
        subprocess.run(cli + ["--rules", "clock-discipline",
                              "--update-baseline"],
                       check=True, capture_output=True, timeout=300)
        first = json.loads(baseline.read_text())["findings"]
        assert len(first) == 1 and first[0]["rule"] == "clock-discipline"
        # a scoped update of a DIFFERENT rule preserves it verbatim
        subprocess.run(cli + ["--rules", "lock-discipline",
                              "--update-baseline"],
                       check=True, capture_output=True, timeout=300)
        assert json.loads(baseline.read_text())["findings"] == first
        # re-update of the same rule: the still-firing, now-baselined
        # finding survives instead of being dropped
        subprocess.run(cli + ["--rules", "clock-discipline",
                              "--update-baseline"],
                       check=True, capture_output=True, timeout=300)
        assert json.loads(baseline.read_text())["findings"] == first
        # and with the baseline applied the gate passes
        proc = subprocess.run(cli + ["--rules", "clock-discipline"],
                              capture_output=True, timeout=300)
        assert proc.returncode == 0

    def test_cli_emit_site_table(self):
        proc = subprocess.run(
            [sys.executable, str(ROOT / "tools/flexlint.py"),
             "--emit-site-table"],
            capture_output=True, text=True, cwd=str(ROOT), timeout=300,
        )
        assert proc.returncode == 0
        for site in faults.SITES:
            assert f"| `{site}` |" in proc.stdout
