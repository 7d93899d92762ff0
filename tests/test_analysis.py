"""simlint analyzer tests: per-rule fixtures + repo-wide clean smoke.

Each shipped rule gets (at least) one passing fixture, one violating
fixture, and one suppressed fixture, per the analyzer contract.  The
fixtures are tiny synthetic trees under tmp_path shaped like the real
repo (``repro/sim/...``) so the manifest's path matching engages.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

from repro.analysis import DEFAULT_MANIFEST, analyze_paths, manifest_dict
from repro.analysis.core import SourceFile, analyze_files, default_rules
from repro.analysis.dtype import DtypeDisciplineRule
from repro.analysis.guards import GuardDisciplineRule
from repro.analysis.parity import EngineParityRule
from repro.analysis.purity import JitPurityRule
from repro.analysis.schema import EventSchemaRule

SRC = Path(__file__).resolve().parents[1] / "src"


def _write(tmp_path: Path, rel: str, code: str) -> Path:
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(code)
    return p


def _lint_one(tmp_path, rel, code, rule):
    p = _write(tmp_path, rel, code)
    return analyze_files([SourceFile.load(p)], [rule])


# ---------------------------------------------------------------------------
# guard-discipline
# ---------------------------------------------------------------------------


class TestGuardDiscipline:
    def test_guarded_emit_passes(self, tmp_path):
        code = (
            "class S:\n"
            "    def step(self):\n"
            "        if self.tracer is not None:\n"
            "            self.tracer.emit(ADMIT, 1)\n"
        )
        assert _lint_one(tmp_path, "m.py", code, GuardDisciplineRule()) == []

    def test_and_conjunction_guard_passes(self, tmp_path):
        code = (
            "class S:\n"
            "    def step(self, mask):\n"
            "        if self.tracer is not None and mask.any():\n"
            "            self.tracer.emit(TRUNCATE, 2)\n"
        )
        assert _lint_one(tmp_path, "m.py", code, GuardDisciplineRule()) == []

    def test_early_return_guard_passes(self, tmp_path):
        code = (
            "class S:\n"
            "    def step(self):\n"
            "        if self.tracer is None:\n"
            "            return 0\n"
            "        self.tracer.emit(ARRIVAL, 3)\n"
            "        return 1\n"
        )
        assert _lint_one(tmp_path, "m.py", code, GuardDisciplineRule()) == []

    def test_conditional_expression_guard_passes(self, tmp_path):
        code = (
            "class S:\n"
            "    def tick(self, t):\n"
            "        return self.telemetry.sample(t) "
            "if self.telemetry is not None else None\n"
        )
        assert _lint_one(tmp_path, "m.py", code, GuardDisciplineRule()) == []

    def test_unguarded_emit_flagged(self, tmp_path):
        code = (
            "class S:\n"
            "    def step(self):\n"
            "        self.tracer.emit(ADMIT, 1)\n"
        )
        fs = _lint_one(tmp_path, "m.py", code, GuardDisciplineRule())
        assert len(fs) == 1
        assert fs[0].rule == "guard-discipline"
        assert fs[0].line == 3

    def test_wrong_receiver_guard_flagged(self, tmp_path):
        code = (
            "class S:\n"
            "    def step(self):\n"
            "        if self.telemetry is not None:\n"
            "            self.tracer.emit(ADMIT, 1)\n"
        )
        fs = _lint_one(tmp_path, "m.py", code, GuardDisciplineRule())
        assert len(fs) == 1

    def test_nested_function_must_reguard(self, tmp_path):
        code = (
            "class S:\n"
            "    def step(self):\n"
            "        if self.tracer is not None:\n"
            "            def inner():\n"
            "                self.tracer.emit(ADMIT, 1)\n"
        )
        fs = _lint_one(tmp_path, "m.py", code, GuardDisciplineRule())
        assert len(fs) == 1

    def test_fault_runtime_any_method_watched(self, tmp_path):
        code = (
            "class S:\n"
            "    def route(self, t):\n"
            "        return self._fault_rt.blocked(t)\n"
        )
        fs = _lint_one(tmp_path, "m.py", code, GuardDisciplineRule())
        assert len(fs) == 1

    def test_suppression_honored(self, tmp_path):
        code = (
            "class S:\n"
            "    def step(self):\n"
            "        self.tracer.emit(ADMIT, 1)"
            "  # simlint: disable=guard-discipline\n"
        )
        assert _lint_one(tmp_path, "m.py", code, GuardDisciplineRule()) == []


# ---------------------------------------------------------------------------
# dtype-discipline
# ---------------------------------------------------------------------------

JAX_ENGINE = "repro/sim/jax_engine.py"


class TestDtypeDiscipline:
    def test_explicit_f64_passes(self, tmp_path):
        code = (
            "import jax.numpy as jnp\n"
            "f64 = jnp.float64\n"
            "x = jnp.zeros((4,), f64)\n"
            "y = jnp.asarray(0, jnp.int32)\n"
        )
        assert _lint_one(tmp_path, JAX_ENGINE, code, DtypeDisciplineRule()) == []

    def test_float32_reference_flagged(self, tmp_path):
        code = "import jax.numpy as jnp\nx = q.astype(jnp.float32)\n"
        fs = _lint_one(tmp_path, JAX_ENGINE, code, DtypeDisciplineRule())
        assert len(fs) == 1 and "float32" in fs[0].message

    def test_float32_outside_critical_file_ignored(self, tmp_path):
        code = "import jax.numpy as jnp\nx = q.astype(jnp.float32)\n"
        assert (
            _lint_one(tmp_path, "repro/other.py", code, DtypeDisciplineRule())
            == []
        )

    def test_manifest_scope_allowance(self, tmp_path):
        code = (
            "import jax.numpy as jnp\n"
            "def window_step(c):\n"
            "    return c.astype(jnp.float32)\n"
        )
        assert _lint_one(tmp_path, JAX_ENGINE, code, DtypeDisciplineRule()) == []

    def test_bare_float_literal_constructor_flagged(self, tmp_path):
        code = "import jax.numpy as jnp\nx = jnp.asarray(1e-9)\n"
        fs = _lint_one(tmp_path, JAX_ENGINE, code, DtypeDisciplineRule())
        assert len(fs) == 1 and "float literal" in fs[0].message

    def test_implicit_dtype_zeros_flagged(self, tmp_path):
        code = "import jax.numpy as jnp\nx = jnp.zeros((4,))\n"
        fs = _lint_one(tmp_path, JAX_ENGINE, code, DtypeDisciplineRule())
        assert len(fs) == 1

    def test_unwrapped_roofline_constant_flagged(self, tmp_path):
        code = "def f(timing):\n    return timing.w_base * 2\n"
        fs = _lint_one(tmp_path, JAX_ENGINE, code, DtypeDisciplineRule())
        assert len(fs) == 1 and "w_base" in fs[0].message

    def test_wrapped_roofline_constant_passes(self, tmp_path):
        code = "def f(timing):\n    return float(timing.w_base) * 2\n"
        assert _lint_one(tmp_path, JAX_ENGINE, code, DtypeDisciplineRule()) == []

    def test_x64_entry_outside_context_flagged(self, tmp_path):
        code = "def go(spec):\n    return _runner(spec)\n"
        fs = _lint_one(tmp_path, JAX_ENGINE, code, DtypeDisciplineRule())
        assert len(fs) == 1 and "enable_x64" in fs[0].message

    def test_x64_entry_inside_context_passes(self, tmp_path):
        code = (
            "import jax\n"
            "def go(spec):\n"
            "    with jax.enable_x64():\n"
            "        return _runner(spec)\n"
        )
        assert _lint_one(tmp_path, JAX_ENGINE, code, DtypeDisciplineRule()) == []

    def test_suppression_honored(self, tmp_path):
        code = (
            "import jax.numpy as jnp\n"
            "x = q.astype(jnp.float32)"
            "  # simlint: disable=dtype-discipline\n"
        )
        assert _lint_one(tmp_path, JAX_ENGINE, code, DtypeDisciplineRule()) == []


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------


class TestJitPurity:
    def test_clean_jit_body_passes(self, tmp_path):
        code = (
            "import jax\n"
            "def core(c):\n"
            "    return c + 1\n"
            "fn = jax.jit(core)\n"
        )
        assert _lint_one(tmp_path, "m.py", code, JitPurityRule()) == []

    def test_clock_in_jit_body_flagged(self, tmp_path):
        code = (
            "import jax, time\n"
            "def core(c):\n"
            "    t = time.time()\n"
            "    return c + t\n"
            "fn = jax.jit(core)\n"
        )
        fs = _lint_one(tmp_path, "m.py", code, JitPurityRule())
        assert len(fs) == 1 and "time.time" in fs[0].message

    def test_print_in_while_loop_body_flagged(self, tmp_path):
        code = (
            "from jax import lax\n"
            "def body(c):\n"
            "    print(c)\n"
            "    return c\n"
            "out = lax.while_loop(lambda c: c < 3, body, 0)\n"
        )
        fs = _lint_one(tmp_path, "m.py", code, JitPurityRule())
        assert len(fs) == 1 and "print" in fs[0].message

    def test_transitive_callee_checked(self, tmp_path):
        code = (
            "import jax\n"
            "def helper(x):\n"
            "    print(x)\n"
            "    return x\n"
            "def core(c):\n"
            "    return helper(c)\n"
            "fn = jax.jit(core)\n"
        )
        fs = _lint_one(tmp_path, "m.py", code, JitPurityRule())
        assert len(fs) == 1

    def test_decorated_partial_jit_detected(self, tmp_path):
        code = (
            "import functools, jax\n"
            "@functools.partial(jax.jit, static_argnames=('n',))\n"
            "def core(c, n):\n"
            "    global COUNT\n"
            "    return c\n"
        )
        fs = _lint_one(tmp_path, "m.py", code, JitPurityRule())
        assert len(fs) == 1 and "global" in fs[0].message

    def test_while_body_arity_flagged(self, tmp_path):
        code = (
            "from jax import lax\n"
            "def body(a, b):\n"
            "    return a\n"
            "out = lax.while_loop(lambda c: True, body, 0)\n"
        )
        fs = _lint_one(tmp_path, "m.py", code, JitPurityRule())
        assert any("one carry parameter" in f.message for f in fs)

    def test_bare_return_in_while_body_flagged(self, tmp_path):
        code = (
            "from jax import lax\n"
            "def body(c):\n"
            "    if c:\n"
            "        return\n"
            "    return c\n"
            "out = lax.while_loop(lambda c: True, body, 0)\n"
        )
        fs = _lint_one(tmp_path, "m.py", code, JitPurityRule())
        assert any("bare `return`" in f.message for f in fs)

    def test_legacy_global_rng_flagged_anywhere(self, tmp_path):
        code = "import numpy as np\nx = np.random.rand(4)\n"
        fs = _lint_one(tmp_path, "m.py", code, JitPurityRule())
        assert len(fs) == 1 and "np.random.rand" in fs[0].message

    def test_seeded_generator_passes(self, tmp_path):
        code = "import numpy as np\nrng = np.random.default_rng(0)\n"
        assert _lint_one(tmp_path, "m.py", code, JitPurityRule()) == []

    def test_suppression_honored(self, tmp_path):
        code = (
            "import numpy as np\n"
            "x = np.random.rand(4)  # simlint: disable=jit-purity\n"
        )
        assert _lint_one(tmp_path, "m.py", code, JitPurityRule()) == []


# ---------------------------------------------------------------------------
# engine-parity (project rule, fixture engine trio + fleet)
# ---------------------------------------------------------------------------

REF_ENGINE_OK = """
class PoolSim:
    def step(self):
        self.preemption_count += 1
        self.rejection_count += 1
        self.truncation_count += 1
        if self.tracer is not None:
            self.tracer.emit(ADMIT, 1)
            self.tracer.emit(PREEMPT, 1)
            self.tracer.emit(TRUNCATE, 1)
            self.tracer.emit(REJECT, 1)
"""

JAX_ENGINE_OK = """
def init_pool():
    return {"npre": 0, "nrej": 0, "ntr": 0}

def update(st):
    return {"npre": st["npre"] + 1, "nrej": st["nrej"] + 1,
            "ntr": st["ntr"] + 1}

def run_fleet_jax(fleet):
    return FleetResult(
        summary=1, per_pool=2, router_stats=3, preemptions=4,
        rejections=5, truncations=6, telemetry=None, slo=None,
    )
"""

FLEET_OK = """
def _run_reference(self):
    return FleetResult(
        summary=1, per_pool=2, router_stats=3, preemptions=4,
        rejections=5, truncations=6, retries=0, timeouts=0, shed=0,
        instance_failures=0, availability=1.0, records=[],
        fail_records=[], telemetry=None, slo=None,
    )

def _run_vectorized(self):
    return FleetResult(
        summary=1, per_pool=2, router_stats=3, preemptions=4,
        rejections=5, truncations=6, retries=0, timeouts=0, shed=0,
        instance_failures=0, availability=1.0,
        fail_records=[], telemetry=None, slo=None,
    )
"""


def _parity_tree(tmp_path, vec_engine=REF_ENGINE_OK, fleet=FLEET_OK):
    files = [
        _write(tmp_path, "repro/sim/engine.py", REF_ENGINE_OK),
        _write(tmp_path, "repro/sim/vector_engine.py", vec_engine),
        _write(tmp_path, "repro/sim/jax_engine.py", JAX_ENGINE_OK),
        _write(tmp_path, "repro/sim/fleet.py", fleet),
    ]
    return [SourceFile.load(p) for p in files]


class TestEngineParity:
    def test_aligned_trio_passes(self, tmp_path):
        files = _parity_tree(tmp_path)
        assert analyze_files(files, [EngineParityRule()]) == []

    def test_missing_counter_flagged(self, tmp_path):
        vec = REF_ENGINE_OK.replace("self.truncation_count += 1\n        ", "")
        files = _parity_tree(tmp_path, vec_engine=vec)
        fs = analyze_files(files, [EngineParityRule()])
        assert any(
            "truncation_count" in f.message
            and f.path.endswith("vector_engine.py")
            for f in fs
        )

    def test_unknown_counter_flagged(self, tmp_path):
        vec = REF_ENGINE_OK.replace(
            "self.truncation_count += 1",
            "self.truncation_count += 1\n        self.mystery_count += 1",
        )
        files = _parity_tree(tmp_path, vec_engine=vec)
        fs = analyze_files(files, [EngineParityRule()])
        assert any("mystery_count" in f.message for f in fs)

    def test_missing_event_kind_flagged(self, tmp_path):
        vec = REF_ENGINE_OK.replace("self.tracer.emit(PREEMPT, 1)\n            ", "")
        files = _parity_tree(tmp_path, vec_engine=vec)
        fs = analyze_files(files, [EngineParityRule()])
        assert any("preempt" in f.message for f in fs)

    def test_fleet_result_drift_flagged(self, tmp_path):
        fleet = FLEET_OK.replace("availability=1.0,\n        fail_records=[], ", "")
        files = _parity_tree(tmp_path, fleet=fleet)
        fs = analyze_files(files, [EngineParityRule()])
        missing = {m for f in fs for m in ("availability", "fail_records")
                   if m in f.message}
        assert missing == {"availability", "fail_records"}

    def test_manifest_tolerates_jax_omissions(self, tmp_path):
        # the jax fixture omits retries/timeouts/records/... — all of it
        # declared in fleet_result.missing_ok, so the aligned tree is clean
        files = _parity_tree(tmp_path)
        assert analyze_files(files, [EngineParityRule()]) == []

    def test_suppression_honored(self, tmp_path):
        vec = REF_ENGINE_OK.replace(
            "self.truncation_count += 1",
            "self.truncation_count += 1\n        "
            "self.mystery_count += 1  # simlint: disable=engine-parity",
        )
        files = _parity_tree(tmp_path, vec_engine=vec)
        assert analyze_files(files, [EngineParityRule()]) == []

    def test_partial_tree_skips(self, tmp_path):
        p = _write(tmp_path, "repro/sim/engine.py", REF_ENGINE_OK)
        assert analyze_files([SourceFile.load(p)], [EngineParityRule()]) == []


# ---------------------------------------------------------------------------
# event-schema (project rule, fixture obs trio)
# ---------------------------------------------------------------------------

EVENTS_OK = """
ARRIVAL, ADMIT, REJECT, CALIB_SYNC = range(4)
EVENT_NAMES = ("arrival", "admit", "reject", "calib_sync")
"""

EMITTER_OK = """
class S:
    def step(self):
        if self.tracer is not None:
            self.tracer.emit(ARRIVAL, 1)
            self.tracer.emit(ADMIT, 1)
            self.tracer.emit(REJECT, 1)
            self.tracer.emit(CALIB_SYNC, 1)
"""

VALIDATE_OK = """
REQUIRED_COLUMNS = ("t_sim",)
POOL_COLUMNS = ("queue_depth", "active")
REQUIRED_COLUMNS_V2 = ("retries",)
POOL_COLUMNS_V2 = ("down",)
"""

TIMESERIES_OK = """
class T:
    def sample(self, name):
        self.columns["t_sim"].append(0)
        self.columns["retries"].append(0)
        self.columns[f"queue_depth.{name}"].append(0)
        self.columns[f"active.{name}"].append(0)
        self.columns[f"down.{name}"].append(0)
"""


def _schema_manifest():
    m = manifest_dict()
    m["telemetry"]["emitter_files"] = ["repro/sim/engine.py"]
    m["telemetry"]["unvalidated_families_ok"] = {}
    return m


def _schema_tree(tmp_path, events=EVENTS_OK, emitter=EMITTER_OK,
                 validate=VALIDATE_OK, timeseries=TIMESERIES_OK):
    files = [
        _write(tmp_path, "repro/obs/events.py", events),
        _write(tmp_path, "repro/sim/engine.py", emitter),
        _write(tmp_path, "repro/obs/validate.py", validate),
        _write(tmp_path, "repro/obs/timeseries.py", timeseries),
    ]
    return [SourceFile.load(p) for p in files]


class TestEventSchema:
    def test_wired_tree_passes(self, tmp_path):
        files = _schema_tree(tmp_path)
        assert analyze_files(files, [EventSchemaRule(_schema_manifest())]) == []

    def test_name_order_mismatch_flagged(self, tmp_path):
        ev = EVENTS_OK.replace('"admit", "reject"', '"reject", "admit"')
        files = _schema_tree(tmp_path, events=ev)
        fs = analyze_files(files, [EventSchemaRule(_schema_manifest())])
        assert any("mismatch" in f.message for f in fs)

    def test_arity_mismatch_flagged(self, tmp_path):
        ev = EVENTS_OK.replace(', "calib_sync"', "")
        files = _schema_tree(tmp_path, events=ev)
        fs = analyze_files(files, [EventSchemaRule(_schema_manifest())])
        assert any("EVENT_NAMES" in f.message for f in fs)

    def test_dead_kind_flagged(self, tmp_path):
        em = EMITTER_OK.replace("self.tracer.emit(CALIB_SYNC, 1)\n", "pass\n")
        files = _schema_tree(tmp_path, emitter=em)
        fs = analyze_files(files, [EventSchemaRule(_schema_manifest())])
        assert any("CALIB_SYNC" in f.message and "declared but" in f.message
                   for f in fs)

    def test_undeclared_kind_flagged(self, tmp_path):
        em = EMITTER_OK.replace(
            "self.tracer.emit(CALIB_SYNC, 1)",
            "self.tracer.emit(CALIB_SYNC, 1)\n"
            "            self.tracer.emit(MYSTERY, 1)",
        )
        files = _schema_tree(tmp_path, emitter=em)
        fs = analyze_files(files, [EventSchemaRule(_schema_manifest())])
        assert any("MYSTERY" in f.message for f in fs)

    def test_validator_only_column_flagged(self, tmp_path):
        va = VALIDATE_OK.replace('"queue_depth", "active"',
                                 '"queue_depth", "active", "bogus"')
        files = _schema_tree(tmp_path, validate=va)
        fs = analyze_files(files, [EventSchemaRule(_schema_manifest())])
        assert any('"bogus"' in f.message for f in fs)

    def test_unvalidated_family_flagged_then_tolerated(self, tmp_path):
        ts = TIMESERIES_OK.replace(
            'self.columns[f"down.{name}"].append(0)',
            'self.columns[f"down.{name}"].append(0)\n'
            '        self.columns[f"mystery.{name}"].append(0)',
        )
        files = _schema_tree(tmp_path, timeseries=ts)
        fs = analyze_files(files, [EventSchemaRule(_schema_manifest())])
        assert any('"mystery.*"' in f.message for f in fs)
        m = _schema_manifest()
        m["telemetry"]["unvalidated_families_ok"] = {"mystery": "fixture"}
        assert analyze_files(files, [EventSchemaRule(m)]) == []

    def test_suppression_honored(self, tmp_path):
        # dead-kind finding anchors at the constants line in events.py
        ev = EVENTS_OK.replace(
            "ARRIVAL, ADMIT, REJECT, CALIB_SYNC = range(4)",
            "ARRIVAL, ADMIT, REJECT, CALIB_SYNC = range(4)"
            "  # simlint: disable=event-schema",
        )
        em = EMITTER_OK.replace("self.tracer.emit(CALIB_SYNC, 1)\n", "pass\n")
        files = _schema_tree(tmp_path, events=ev, emitter=em)
        assert analyze_files(files, [EventSchemaRule(_schema_manifest())]) == []


# ---------------------------------------------------------------------------
# repo-wide smoke + CLI + manifest
# ---------------------------------------------------------------------------


class TestRepoClean:
    def test_simlint_clean_on_repo(self):
        findings = analyze_paths([SRC / "repro"])
        assert findings == [], "\n".join(f.format() for f in findings)

    def test_default_rules_cover_contract(self):
        names = {r.name for r in default_rules()}
        assert names == {
            "engine-parity",
            "guard-discipline",
            "dtype-discipline",
            "jit-purity",
            "event-schema",
        }

    def test_manifest_reasons_present(self):
        # every tolerance is a documented statement: reasons are non-empty
        ev = DEFAULT_MANIFEST["events"]["missing_ok"]
        fr = DEFAULT_MANIFEST["fleet_result"]["missing_ok"]
        dt = DEFAULT_MANIFEST["dtype"]["float32_scope_ok"]
        tl = DEFAULT_MANIFEST["telemetry"]["unvalidated_families_ok"]
        for table in (*ev.values(), *fr.values(), *dt.values(), tl):
            for reason in table.values():
                assert isinstance(reason, str) and reason.strip()


class TestCli:
    def _run(self, args, cwd):
        env = dict(os.environ)
        env["PYTHONPATH"] = str(SRC)
        return subprocess.run(
            [sys.executable, "-m", "repro.analysis", *args],
            capture_output=True,
            text=True,
            env=env,
            cwd=cwd,
        )

    def test_clean_dir_exit_zero_with_json(self, tmp_path):
        _write(tmp_path, "pkg/ok.py", "x = 1\n")
        out = tmp_path / "report.json"
        res = self._run([str(tmp_path / "pkg"), "--json", str(out)], tmp_path)
        assert res.returncode == 0, res.stderr
        report = json.loads(out.read_text())
        assert report["schema"] == "repro.simlint/report-v1"
        assert report["findings"] == []
        assert report["manifest"]["schema"] == "repro.simlint/manifest-v1"
        assert {r["name"] for r in report["rules"]} >= {"engine-parity"}

    def test_violating_dir_exit_one(self, tmp_path):
        _write(
            tmp_path,
            "pkg/bad.py",
            "class S:\n    def f(self):\n        self.tracer.emit(A, 1)\n",
        )
        out = tmp_path / "report.json"
        res = self._run([str(tmp_path / "pkg"), "--json", str(out)], tmp_path)
        assert res.returncode == 1
        report = json.loads(out.read_text())
        assert len(report["findings"]) == 1
        assert report["findings"][0]["rule"] == "guard-discipline"
        assert "hint" in report["findings"][0]

    def test_list_rules(self, tmp_path):
        res = self._run(["--list-rules"], tmp_path)
        assert res.returncode == 0
        assert "guard-discipline" in res.stdout
        assert "event-schema" in res.stdout

    def test_manifest_dump(self, tmp_path):
        res = self._run(["--manifest"], tmp_path)
        assert res.returncode == 0
        blob = json.loads(res.stdout)
        assert blob["schema"] == "repro.simlint/manifest-v1"
        assert set(blob["counters"]) == {
            "preemption_count",
            "rejection_count",
            "truncation_count",
        }
