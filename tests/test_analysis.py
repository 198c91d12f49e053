"""Static analysis subsystem (ISSUE 4): source passes, program passes,
contracts, CLI, baselines, and strict mode.

The per-rule fixtures live in tests/analysis_fixtures/ — one known-positive
and one known-negative file per rule ID, so every rule's firing condition
AND its non-firing idiom are pinned. The self-lint test is the CI gate: the
source passes run in-process over accelerate_tpu/ against the checked-in
baseline (tests/analysis_baseline.json), so any NEW finding fails tier-1.
"""

import json
import os
import subprocess
import sys
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from accelerate_tpu.analysis import (
    AnalysisViolation,
    CollectiveContract,
    RULES,
    audit_replication,
    collective_counts,
    contract_for,
    find_host_transfers,
    lint_file,
    lint_paths,
    lint_target,
    lint_text,
    new_findings,
    render_human,
    render_json,
    save_baseline,
)
from accelerate_tpu.commands.accelerate_cli import main as cli_main

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(TESTS_DIR)
FIXTURES = os.path.join(TESTS_DIR, "analysis_fixtures")
BASELINE = os.path.join(TESTS_DIR, "analysis_baseline.json")

ALL_RULE_IDS = [f"ATP00{i}" for i in range(1, 9)]
# ATP2xx (ISSUE 13): the lifecycle auditor — paired resources, request
# FSM, thread confinement — same fixture scheme, same pipeline
LIFECYCLE_RULE_IDS = ["ATP201", "ATP202", "ATP203",
                      "ATP211", "ATP212", "ATP221"]
# ATP3xx (ISSUE 19): the concurrency auditor — shared-state locksets,
# lock-order cycles, blocking calls on the loop, condvar protocol,
# thread shutdown — same fixture scheme, same pipeline
CONCURRENCY_RULE_IDS = ["ATP301", "ATP302", "ATP303", "ATP304", "ATP305"]


# ---------------------------------------------------------------------------
# source passes: one positive + one negative fixture per rule
# ---------------------------------------------------------------------------


class TestSourceRules:
    @pytest.mark.parametrize("rule", ALL_RULE_IDS + LIFECYCLE_RULE_IDS
                             + CONCURRENCY_RULE_IDS)
    def test_positive_fixture_fires(self, rule):
        path = os.path.join(FIXTURES, f"{rule.lower()}_pos.py")
        got = {f.rule for f in lint_file(path)}
        assert rule in got, f"{path} did not produce {rule} (got {got})"

    @pytest.mark.parametrize("rule", ALL_RULE_IDS + LIFECYCLE_RULE_IDS
                             + CONCURRENCY_RULE_IDS)
    def test_negative_fixture_is_clean(self, rule):
        path = os.path.join(FIXTURES, f"{rule.lower()}_neg.py")
        found = [f for f in lint_file(path) if f.rule == rule]
        assert not found, (
            f"false positive: {path} produced "
            f"{[f.render() for f in found]}"
        )

    def test_parse_error_is_a_finding_not_a_crash(self):
        findings = lint_text("def broken(:\n", "broken.py")
        assert [f.rule for f in findings] == ["ATP000"]

    def test_rule_catalog_is_stable(self):
        """Rule IDs are public API: renumbering breaks suppressions and
        baselines in user trees."""
        for rid in ALL_RULE_IDS + ["ATP000", "ATP101", "ATP102", "ATP103"]:
            assert rid in RULES
        assert RULES["ATP001"].name == "host-sync-item"
        assert RULES["ATP101"].name == "collective-contract"

    def test_host_code_is_never_linted(self):
        """The same hazards OUTSIDE traced code are legitimate host idioms."""
        src = (
            "import numpy as np\n"
            "def metrics_loop(history):\n"
            "    v = history[-1].item()\n"
            "    arr = np.asarray(history)\n"
            "    print(arr)\n"
            "    if v > 0:\n"
            "        np.random.seed(0)\n"
            "    return float(v)\n"
        )
        assert lint_text(src, "host.py") == []


class TestScalarAnnotations:
    def test_float_annotated_param_stays_tainted(self):
        """`x: float` on a jitted fn is a traced weak-typed scalar (loss
        scale, temperature — the classic branch-on-a-tracer hazards);
        unlike int/str/bool config annotations it must stay tainted."""
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def step(state, loss_scale: float):\n"
            "    if loss_scale > 0:\n"
            "        return state\n"
            "    return state\n"
        )
        assert "ATP006" in {f.rule for f in lint_text(src, "t.py")}
        src_int = src.replace("loss_scale: float", "n_layers: int")
        assert "ATP006" not in {f.rule for f in lint_text(src_int, "t.py")}


class TestSuppression:
    def test_line_and_file_suppression(self):
        findings = lint_file(os.path.join(FIXTURES, "suppressed.py"))
        # file-wide ATP004 gone, line-suppressed ATP001 gone; the
        # unsuppressed .item() in g() must survive
        assert [f.rule for f in findings] == ["ATP001"]
        (f,) = findings
        assert "item" in f.source

    def test_bare_disable_suppresses_all_rules_on_line(self):
        src = (
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return float(x.sum().item())  # atp: disable\n"
        )
        from accelerate_tpu.analysis import apply_suppressions

        assert apply_suppressions(lint_text(src, "t.py"), src) == []

    def test_prose_mention_of_syntax_does_not_suppress(self):
        """The directive must END its line: a comment or docstring that
        merely *documents* `# atp: disable-file` (trailing text) must not
        silently suppress the whole file."""
        from accelerate_tpu.analysis import apply_suppressions
        from accelerate_tpu.analysis.findings import parse_suppressions

        src = (
            '"""Docs: `# atp: disable-file` suppresses file-wide."""\n'
            "# the `# atp: disable=ATP001` marker goes at line end\n"
            "import jax\n"
            "@jax.jit\n"
            "def f(x):\n"
            "    return x.sum().item()\n"
        )
        file_rules, per_line = parse_suppressions(src)
        assert file_rules == set() and per_line == {}
        assert [f.rule for f in
                apply_suppressions(lint_text(src, "t.py"), src)] == ["ATP001"]
        # the suppression module's own documentation must not disarm it
        import accelerate_tpu.analysis.findings as findings_mod

        with open(findings_mod.__file__) as fh:
            own_file_rules, _ = parse_suppressions(fh.read())
        assert own_file_rules == set()


class TestBaseline:
    def test_roundtrip_and_new_finding_detection(self, tmp_path):
        pos = os.path.join(FIXTURES, "atp001_pos.py")
        findings = lint_file(pos, root=REPO)
        assert findings
        bl = tmp_path / "bl.json"
        save_baseline(str(bl), findings)
        data = json.loads(bl.read_text())
        assert data["version"] == 1
        # everything accepted -> nothing new
        assert new_findings(findings, data) == []
        # one extra occurrence of the same pattern overflows its count
        assert len(new_findings(findings + findings[:1], data)) == 1
        # a different rule is always new
        other = lint_file(os.path.join(FIXTURES, "atp005_pos.py"), root=REPO)
        assert new_findings(other, data) == other

    def test_fingerprints_survive_line_drift(self):
        src = "import jax\n@jax.jit\ndef f(x):\n    return x.item()\n"
        moved = "import jax\n\n\n@jax.jit\ndef f(x):\n    return x.item()\n"
        (a,) = lint_text(src, "t.py")
        (b,) = lint_text(moved, "t.py")
        assert a.line != b.line and a.fingerprint == b.fingerprint


# ---------------------------------------------------------------------------
# CLI: exit codes 0/1/2, json format, module targets, baseline flags
# ---------------------------------------------------------------------------


class TestLintCLI:
    def test_findings_exit_1_human(self, capsys):
        rc = cli_main(["lint", os.path.join(FIXTURES, "atp001_pos.py")])
        out = capsys.readouterr().out
        assert rc == 1
        assert "ATP001" in out and "host-sync-item" in out

    def test_clean_exit_0(self, capsys):
        rc = cli_main(["lint", os.path.join(FIXTURES, "atp001_neg.py")])
        assert rc == 0
        assert "0 finding(s)" in capsys.readouterr().out

    def test_internal_error_exit_2(self, capsys):
        rc = cli_main(["lint", "/nonexistent/not_a_module_either"])
        assert rc == 2
        assert "internal error" in capsys.readouterr().err

    def test_unknown_rule_exit_2(self, capsys):
        rc = cli_main(["lint", FIXTURES, "--rules", "ATP999"])
        assert rc == 2

    def test_json_format_is_machine_readable(self, capsys):
        rc = cli_main(["lint", os.path.join(FIXTURES, "atp008_pos.py"),
                       "--format", "json"])
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        assert payload["summary"]["count"] >= 1
        assert payload["summary"]["by_rule"].get("ATP008") == 1
        (f,) = [x for x in payload["findings"] if x["rule"] == "ATP008"]
        assert f["line"] > 0 and f["fingerprint"]
        assert payload["rules"]["ATP008"]["name"] == "donation-aliasing"

    def test_module_target_resolution(self, capsys):
        rc = cli_main(["lint", "accelerate_tpu.analysis"])
        assert rc == 0

    def test_baseline_workflow(self, tmp_path, capsys):
        bl = str(tmp_path / "bl.json")
        rc = cli_main(["lint", FIXTURES, "--root", REPO,
                       "--write-baseline", bl])
        assert rc == 0 and os.path.exists(bl)
        capsys.readouterr()
        rc = cli_main(["lint", FIXTURES, "--root", REPO, "--baseline", bl])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "accepted by baseline" in out

    def test_rule_selection(self, capsys):
        rc = cli_main(["lint", os.path.join(FIXTURES, "atp002_pos.py"),
                       "--rules", "ATP006", "--format", "json"])
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        assert set(payload["summary"]["by_rule"]) == {"ATP006"}

    def test_lint_does_not_initialize_a_backend(self):
        """`accelerate-tpu lint` must run on boxes that cannot init an
        accelerator backend (same guard as the telemetry import test)."""
        code = (
            "from accelerate_tpu.commands.accelerate_cli import main\n"
            f"rc = main(['lint', {FIXTURES!r}])\n"
            "assert rc == 1, rc\n"
            "import sys\n"
            "if 'jax' in sys.modules:\n"
            "    from jax._src import xla_bridge\n"
            "    assert not xla_bridge.backends_are_initialized(), (\n"
            "        'lint initialized a jax backend')\n"
        )
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120,
                             cwd=REPO, stdin=subprocess.DEVNULL)
        assert out.returncode == 0, out.stderr[-2000:]

    def test_python_m_lint_is_not_a_silent_noop(self):
        """`python -m accelerate_tpu.commands.lint` must lint, not import-and-
        exit-0 — a CI job wired that way would otherwise always pass."""
        out = subprocess.run(
            [sys.executable, "-m", "accelerate_tpu.commands.lint",
             os.path.join(FIXTURES, "atp001_pos.py")],
            capture_output=True, text=True, timeout=120, cwd=REPO,
            stdin=subprocess.DEVNULL)
        assert out.returncode == 1, (out.returncode, out.stderr[-2000:])
        assert "ATP001" in out.stdout


# ---------------------------------------------------------------------------
# the CI gates: self-lint + examples false-positive guard
# ---------------------------------------------------------------------------


class TestSelfLint:
    def test_accelerate_tpu_clean_against_checked_in_baseline(self):
        """THE tier-1 gate: new findings in accelerate_tpu/ fail CI. Runs
        in-process (AST only), so the gate is cheap."""
        t0 = time.monotonic()
        _, fresh = lint_target(
            os.path.join(REPO, "accelerate_tpu"), root=REPO,
            baseline=BASELINE)
        elapsed = time.monotonic() - t0
        assert fresh == [], (
            "NEW static-analysis findings (fix them, suppress with a "
            "justified `# atp: disable=`, or re-baseline via `accelerate-tpu "
            "lint accelerate_tpu --root . --write-baseline "
            "tests/analysis_baseline.json`):\n" + render_human(fresh)
        )
        assert elapsed < 10.0, f"self-lint took {elapsed:.1f}s; gate must stay cheap"

    def test_self_lint_gate_covers_the_server_package(self):
        """ISSUE 7: the gate's tree walk must include the HTTP front door
        (accelerate_tpu/server/) — if the walker ever grew an exclusion
        that swallowed it, new server hazards would ship unlinted."""
        from accelerate_tpu.analysis.runner import iter_python_files

        files = iter_python_files(os.path.join(REPO, "accelerate_tpu"))
        server_files = [f for f in files
                       if os.sep + "server" + os.sep in f]
        assert any(f.endswith("http.py") for f in server_files), \
            "accelerate_tpu/server must be inside the self-lint tree"
        assert any(f.endswith("service.py") for f in server_files)

    def test_self_lint_gate_covers_the_pod_package(self):
        """ISSUE 13: serving/pod/ is where the lifecycle passes found
        their genuine bugs — a tree-walk exclusion that silently dropped
        it would un-audit exactly the router code the ATP2xx family
        exists for."""
        from accelerate_tpu.analysis.runner import iter_python_files

        files = iter_python_files(os.path.join(REPO, "accelerate_tpu"))
        pod_files = [f for f in files
                     if (os.sep + "serving" + os.sep + "pod" + os.sep) in f]
        for name in ("droute.py", "transfer.py", "mesh.py"):
            assert any(f.endswith(name) for f in pod_files), \
                f"serving/pod/{name} must be inside the self-lint tree"

    def test_self_lint_gate_runs_the_lifecycle_rules(self):
        """The gate runs with NO rule restriction, so the ATP2xx passes
        are part of it by construction — pinned by planting a
        known-leaky file next to the tree and asserting lint_target's
        pipeline reports its ATP201."""
        for rid in LIFECYCLE_RULE_IDS:
            assert rid in RULES, rid
        findings = lint_paths(
            [os.path.join(FIXTURES, "atp201_pos.py")], root=REPO)
        assert any(f.rule == "ATP201" for f in findings)

    def test_examples_are_clean(self):
        """False-positive guard: examples/ is idiomatic user code — the
        linter flagging any of it means a rule is too aggressive."""
        findings = lint_paths([os.path.join(REPO, "examples")], root=REPO)
        assert findings == [], render_human(findings)

    def test_render_json_on_empty(self):
        payload = json.loads(render_json([]))
        assert payload["summary"]["count"] == 0


# ---------------------------------------------------------------------------
# ATP2xx lifecycle passes (ISSUE 13)
# ---------------------------------------------------------------------------


class TestLifecyclePasses:
    def test_rule_catalog_is_stable(self):
        assert RULES["ATP201"].name == "lifecycle-leak-on-path"
        assert RULES["ATP211"].name == "terminal-bypasses-finalizer"
        assert RULES["ATP221"].name == "cross-thread-state-mutation"

    def test_pairing_table_one_line_extension(self):
        """The declarative recipe: a NEW resource registers in one
        ResourcePair line and the whole CFG machinery audits it."""
        import ast as ast_mod

        from accelerate_tpu.analysis.lifecycle import (
            PAIRING_TABLE,
            ResourcePair,
            lint_lifecycle,
        )

        table = PAIRING_TABLE + (ResourcePair(
            "shipment-buffer", acquire=("checkout",),
            release=("checkin",), receivers=("shipments",)),)
        src = (
            "class Router:\n"
            "    def leaky(self, req):\n"
            "        buf = self.shipments.checkout(req)\n"
            "        if buf is None:\n"
            "            return None\n"
            "        if req.cancelled:\n"
            "            return False   # leak\n"
            "        self.shipments.checkin(buf)\n"
            "        return True\n"
        )
        findings = []
        lint_lifecycle(ast_mod.parse(src), src, "t.py", src.splitlines(),
                       findings, table=table)
        assert [f.rule for f in findings] == ["ATP201"]
        assert findings[0].data["resource"] == "shipment-buffer"
        # without the extra row the same code is silent
        findings2 = []
        lint_lifecycle(ast_mod.parse(src), src, "t.py", src.splitlines(),
                       findings2)
        assert findings2 == []

    def test_findings_carry_structured_data(self):
        """The JSON satellite: ATP2xx findings name the resource/state
        and the offending path's line span — actionable without
        rereading the pass."""
        fs = [f for f in lint_file(os.path.join(FIXTURES, "atp201_pos.py"))
              if f.rule == "ATP201"]
        assert fs
        for f in fs:
            assert f.data["resource"]
            assert f.data["acquire_line"] >= 1
            lo, hi = f.data["span"]
            assert lo <= hi
        fs = [f for f in lint_file(os.path.join(FIXTURES, "atp212_pos.py"))
              if f.rule == "ATP212"]
        assert fs and fs[0].data["state"] == "EXPIRED"
        assert fs[0].data["target"] == "user"
        # every lifecycle rule keeps the span contract (a consumer may
        # read data["span"] unconditionally)
        for fixture, rule in (("atp202_pos.py", "ATP202"),
                              ("atp203_pos.py", "ATP203"),
                              ("atp211_pos.py", "ATP211"),
                              ("atp221_pos.py", "ATP221")):
            fs = [f for f in lint_file(os.path.join(FIXTURES, fixture))
                  if f.rule == rule]
            assert fs and all(len(f.data["span"]) == 2 for f in fs), rule

    def test_json_output_includes_data(self, capsys):
        rc = cli_main(["lint", os.path.join(FIXTURES, "atp201_pos.py"),
                       "--format", "json"])
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        rows = [f for f in payload["findings"] if f["rule"] == "ATP201"]
        assert rows and all(r["data"]["resource"] for r in rows)
        assert all("span" in r["data"] for r in rows)

    def test_rules_group_alias(self, capsys):
        """`--rules atp2` selects the whole lifecycle family: the ATP001
        fixture is clean under it, the ATP201 fixture is not, and a bad
        token still exits 2."""
        rc = cli_main(["lint", os.path.join(FIXTURES, "atp001_pos.py"),
                       "--rules", "atp2"])
        capsys.readouterr()
        assert rc == 0
        rc = cli_main(["lint", os.path.join(FIXTURES, "atp201_pos.py"),
                       "--rules", "atp2"])
        out = capsys.readouterr().out
        assert rc == 1 and "ATP201" in out
        rc = cli_main(["lint", os.path.join(FIXTURES, "atp201_pos.py"),
                       "--rules", "atp9"])
        assert rc == 2

    def test_regression_shapes_of_the_fixed_bugs(self):
        """The three genuine serving/ findings this PR fixed, as inline
        shapes: reverting any fix re-creates code the self-lint gate
        rejects."""
        # (1) cache.PagedAllocator.allocate pre-fix: a raising on_evict
        # callback between acquire and release leaked the refcounts
        src = (
            "class A:\n"
            "    def allocate(self, request, nodes):\n"
            "        self.index.acquire(nodes)\n"
            "        private = self.pool.alloc(2)\n"
            "        if private is None:\n"
            "            self.on_evict(3)\n"
            "            private = self.pool.alloc(2)\n"
            "        if private is None:\n"
            "            self.index.release(nodes)\n"
            "            return None\n"
            "        return self.build(nodes, private)\n"
        )
        assert "ATP201" in {f.rule for f in lint_text(src, "t.py")}
        # (2) pod router._harvest pre-fix: EXPIRED without shed_code
        src = (
            "class R:\n"
            "    def _finalize(self, r):\n"
            "        self.metrics.observe_request(r)\n"
            "    def harvest(self, user, now):\n"
            "        user.status = RequestStatus.EXPIRED\n"
            "        user.reject_reason = 'worker dropped'\n"
            "        user.finished_at = now\n"
            "        self._finalize(user)\n"
        )
        assert "ATP212" in {f.rule for f in lint_text(src, "t.py")}
        # (3) the PR 6 class: scheduler.submit without a drain
        src = (
            "class E:\n"
            "    def _finalize_request(self, r):\n"
            "        self.metrics.observe_request(r)\n"
            "    def submit(self, req):\n"
            "        self.scheduler.submit(req)\n"
            "        if req.done:\n"
            "            self._finalize_request(req)\n"
            "        return req\n"
        )
        assert "ATP211" in {f.rule for f in lint_text(src, "t.py")}

    def test_suppression_and_baseline_apply_to_lifecycle_rules(self,
                                                               tmp_path):
        """ATP2xx rides the whole existing pipeline: line suppressions
        disarm a finding, baselines accept it."""
        pos = os.path.join(FIXTURES, "atp212_pos.py")
        findings = lint_file(pos, root=REPO)
        assert any(f.rule == "ATP212" for f in findings)
        src = open(pos).read()
        suppressed = src.replace(
            "user.status = RequestStatus.EXPIRED",
            "user.status = RequestStatus.EXPIRED  # atp: disable=ATP212")
        from accelerate_tpu.analysis import apply_suppressions

        left = apply_suppressions(lint_text(suppressed, "t.py"), suppressed)
        assert not any(f.rule == "ATP212" for f in left)
        bl = tmp_path / "bl.json"
        save_baseline(str(bl), findings)
        assert new_findings(findings, json.loads(bl.read_text())) == []


# ---------------------------------------------------------------------------
# ATP3xx concurrency passes (ISSUE 19)
# ---------------------------------------------------------------------------


class TestConcurrencyPasses:
    def test_rule_catalog_is_stable(self):
        assert RULES["ATP301"].name == "shared-state-no-common-lock"
        assert RULES["ATP302"].name == "lock-order-cycle"
        assert RULES["ATP303"].name == "blocking-call-in-async"
        assert RULES["ATP304"].name == "condvar-misuse"
        assert RULES["ATP305"].name == "thread-never-joined"

    def test_self_lint_gate_runs_the_concurrency_rules(self):
        """The gate runs with NO rule restriction, so the ATP3xx passes
        are part of it by construction — pinned the same way the
        lifecycle gate is: lint_paths' full pipeline must report the
        planted fixture's findings."""
        for rid in CONCURRENCY_RULE_IDS:
            assert rid in RULES, rid
        findings = lint_paths(
            [os.path.join(FIXTURES, "atp302_pos.py")], root=REPO)
        assert any(f.rule == "ATP302" for f in findings)
        findings = lint_paths(
            [os.path.join(FIXTURES, "atp301_pos.py")], root=REPO)
        assert any(f.rule == "ATP301" for f in findings)

    def test_findings_carry_structured_data(self):
        """The JSON contract: ATP302 names the full cycle path and the
        participating locks; ATP301 names the attribute, the contexts,
        and each context's locks; ATP303 names the call and the async
        entry path. Every rule keeps the span contract."""
        fs = [f for f in lint_file(os.path.join(FIXTURES, "atp302_pos.py"))
              if f.rule == "ATP302"]
        assert fs
        cycle = fs[0].data["cycle"]
        assert cycle[0] == cycle[-1] and len(cycle) >= 3
        assert set(fs[0].data["locks"]) == {"Pod._books_lock",
                                            "Pod._wire_lock"}
        fs = [f for f in lint_file(os.path.join(FIXTURES, "atp301_pos.py"))
              if f.rule == "ATP301"]
        assert fs and fs[0].data["attribute"] == "books"
        assert len(fs[0].data["contexts"]) >= 2
        assert isinstance(fs[0].data["locks"], dict)
        fs = [f for f in lint_file(os.path.join(FIXTURES, "atp303_pos.py"))
              if f.rule == "ATP303"]
        assert fs
        by_call = {f.data["call"]: f for f in fs}
        assert by_call["time.sleep"].data["async_entry"] == "drive"
        # the sync helper's finding carries the hop path from the loop
        assert by_call["self.inbox.get"].data["via"] == \
            ["drive", "_pump_once"]
        for fixture, rule in (("atp304_pos.py", "ATP304"),
                              ("atp305_pos.py", "ATP305")):
            fs = [f for f in lint_file(os.path.join(FIXTURES, fixture))
                  if f.rule == rule]
            assert fs and all(len(f.data["span"]) == 2 for f in fs), rule

    def test_json_output_includes_data(self, capsys):
        """`--rules atp3 --format json` emits the structured payload the
        acceptance criteria pin: lock names and the cycle path ride
        `data`, and the run exits 1 on findings."""
        rc = cli_main(["lint", os.path.join(FIXTURES, "atp302_pos.py"),
                       "--rules", "atp3", "--format", "json"])
        assert rc == 1
        payload = json.loads(capsys.readouterr().out)
        assert set(payload["summary"]["by_rule"]) == {"ATP302"}
        (row,) = payload["findings"]
        assert row["data"]["cycle"][0] == row["data"]["cycle"][-1]
        assert row["data"]["locks"]

    def test_rules_group_alias(self, capsys):
        """`--rules atp3` selects the whole concurrency family and
        nothing else: the ATP201 fixture is clean under it, every ATP3xx
        fixture is not, and the clean exit is 0."""
        rc = cli_main(["lint", os.path.join(FIXTURES, "atp201_pos.py"),
                       "--rules", "atp3"])
        capsys.readouterr()
        assert rc == 0
        for rid in CONCURRENCY_RULE_IDS:
            rc = cli_main(["lint",
                           os.path.join(FIXTURES, f"{rid.lower()}_pos.py"),
                           "--rules", "atp3"])
            out = capsys.readouterr().out
            assert rc == 1 and rid in out, (rid, out)

    def test_blocking_table_one_line_extension(self):
        """The declarative recipe: a NEW blocking shape registers in one
        BlockingCall row and the reachability machinery audits it."""
        import ast as ast_mod

        from accelerate_tpu.analysis import BLOCKING_CALLS, BlockingCall
        from accelerate_tpu.analysis.concurrency import lint_concurrency

        table = BLOCKING_CALLS + (BlockingCall(
            "fetch_sync", "synchronous RPC stalls the loop"),)
        src = (
            "class S:\n"
            "    async def drive(self):\n"
            "        reply = self.stub.fetch_sync()\n"
        )
        findings = []
        lint_concurrency(ast_mod.parse(src), src, "t.py",
                         src.splitlines(), findings, blocking=table)
        assert [f.rule for f in findings] == ["ATP303"]
        assert findings[0].data["call"] == "self.stub.fetch_sync"
        # without the extra row the same code is silent
        findings2 = []
        lint_concurrency(ast_mod.parse(src), src, "t.py",
                         src.splitlines(), findings2)
        assert findings2 == []

    def test_thread_entries_task_extension(self):
        """ISSUE 19's THREAD_ENTRIES extension: asyncio task creation is
        a concurrent context. Dropping task_constructors from the table
        silences the thread-vs-task race the atp301 fixture pins."""
        import ast as ast_mod

        from accelerate_tpu.analysis import ThreadEntries
        from accelerate_tpu.analysis.concurrency import lint_concurrency

        # plain unlocked writes, one thread + one task: WITH task
        # recognition the pair is thread-vs-task (preemptive race, ours);
        # WITHOUT it the async def is just drive-loop code, which is
        # ATP221's one-thread-vs-drive territory and ATP301 stays silent
        src = (
            "import threading\n"
            "class R:\n"
            "    def start(self, loop):\n"
            "        self._t = threading.Thread(target=self._pump)\n"
            "        self._t.start()\n"
            "        loop.create_task(self._drive())\n"
            "    def _pump(self):\n"
            "        self.depth = 1\n"
            "    async def _drive(self):\n"
            "        self.depth = 2\n"
        )
        tree = ast_mod.parse(src)
        findings = []
        lint_concurrency(tree, src, "t.py", src.splitlines(), findings)
        hits = [f for f in findings if f.rule == "ATP301"]
        assert hits and hits[0].data["attribute"] == "depth"
        assert sorted(hits[0].data["contexts"]) == ["_drive", "_pump"]
        no_tasks = ThreadEntries(task_constructors=())
        findings2 = []
        lint_concurrency(tree, src, "t.py", src.splitlines(), findings2,
                         entries=no_tasks)
        assert not any(f.rule == "ATP301" for f in findings2)

    def test_suppression_and_baseline_apply_to_concurrency_rules(
            self, tmp_path):
        """ATP3xx rides the whole existing pipeline: line suppressions
        disarm a finding, baselines accept it. The tree itself carries a
        justified `# atp: disable=ATP303` (droute's incident-capture
        sleep), so the real-code path is exercised by the self-lint gate
        too."""
        pos = os.path.join(FIXTURES, "atp303_pos.py")
        findings = lint_file(pos, root=REPO)
        assert any(f.rule == "ATP303" for f in findings)
        src = open(pos).read()
        # the directive must END its line, so replace the trailing prose
        suppressed = src.replace(
            "# parks every task on the loop",
            "# atp: disable=ATP303")
        from accelerate_tpu.analysis import apply_suppressions

        left = apply_suppressions(lint_text(suppressed, "t.py"), suppressed)
        assert not any(f.rule == "ATP303" and "sleep" in f.source
                       for f in left)
        bl = tmp_path / "bl.json"
        save_baseline(str(bl), findings)
        assert new_findings(findings, json.loads(bl.read_text())) == []
        # the in-tree justified suppression is really there
        droute = os.path.join(REPO, "accelerate_tpu", "serving", "pod",
                              "distributed", "droute.py")
        assert "# atp: disable=ATP303" in open(droute).read()

    def test_regression_shapes_of_the_fixed_bugs(self):
        """The genuine ATP3xx findings this PR fixed, as inline shapes:
        reverting any fix re-creates code the self-lint gate rejects."""
        # (1) transport.SocketChannel pre-fix: reader/writer threads
        # started in __init__, close() never joined them
        src = (
            "import threading\n"
            "class Chan:\n"
            "    def __init__(self, sock):\n"
            "        self._reader = threading.Thread(target=self._rl)\n"
            "        self._reader.start()\n"
            "    def _rl(self):\n"
            "        pass\n"
            "    def close(self):\n"
            "        self._closed = True\n"
        )
        assert "ATP305" in {f.rule for f in lint_text(src, "t.py")}
        # (2) droute pre-fix: step() slept inline, and astream (an async
        # def) calls step() — a time.sleep on the event loop
        src = (
            "import time\n"
            "class Router:\n"
            "    async def astream(self, req):\n"
            "        while not self.step():\n"
            "            pass\n"
            "    def step(self):\n"
            "        worked = self.pump()\n"
            "        if not worked:\n"
            "            time.sleep(0.001)\n"
            "        return worked\n"
        )
        assert "ATP303" in {f.rule for f in lint_text(src, "t.py")}
        # (3) data._PrefetchIterator pre-fix: worker thread with no
        # close/stop path at all
        src = (
            "import threading\n"
            "class Prefetch:\n"
            "    def __init__(self, it):\n"
            "        self._thread = threading.Thread(target=self._w)\n"
            "        self._thread.start()\n"
            "    def _w(self):\n"
            "        pass\n"
        )
        assert "ATP305" in {f.rule for f in lint_text(src, "t.py")}


# ---------------------------------------------------------------------------
# program passes
# ---------------------------------------------------------------------------


def _psum_program():
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("i",))
    f = jax.shard_map(lambda x: jax.lax.psum(x, "i"), mesh=mesh,
           in_specs=P("i"), out_specs=P())
    return jax.jit(f), jnp.arange(8.0)


def _ring(x):
    return jax.lax.ppermute(x, "i", [(k, (k + 1) % 8) for k in range(8)])


class TestCollectiveCounts:
    def test_counts_from_jaxpr(self):
        fn, x = _psum_program()
        counts = collective_counts(jax.make_jaxpr(fn)(x))
        assert counts["all-reduce"] == 1

    @pytest.mark.parametrize("check_vma", [True, False])
    @pytest.mark.parametrize("body,in_spec,out_spec,expect", [
        (lambda x: jax.lax.psum(x, "i"), P("i"), P(), "all-reduce"),
        (lambda x: jax.lax.pmax(x, "i"), P("i"), P(), "all-reduce"),
        (lambda x: jax.lax.all_gather(x, "i", tiled=True), P("i"), P("i"),
         "all-gather"),
        (lambda x: jax.lax.psum_scatter(x, "i", tiled=True), P(), P("i"),
         "reduce-scatter"),
        (_ring, P("i"), P("i"), "collective-permute"),
    ], ids=["psum", "pmax", "all_gather", "psum_scatter", "ppermute"])
    def test_jaxpr_counts_every_collective_under_both_vma_modes(
            self, body, in_spec, out_spec, expect, check_vma):
        """jax 0.9 renames the shard_map-body primitives when the
        replication check is on (`psum` -> `psum_invariant`): the strict
        jaxpr audit must count each collective under either spelling, not
        silently read 0."""
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("i",))
        fn = jax.shard_map(body, mesh=mesh, in_specs=in_spec,
                           out_specs=out_spec, check_vma=check_vma)
        counts = collective_counts(jax.make_jaxpr(fn)(jnp.arange(64.0)))
        assert counts[expect] == 1, counts

    def test_counts_from_lowered_stablehlo(self):
        fn, x = _psum_program()
        counts = collective_counts(fn.lower(x))
        assert counts["all-reduce"] >= 1

    def test_counts_from_compiled_hlo_text(self):
        fn, x = _psum_program()
        counts = collective_counts(fn.lower(x).compile().as_text())
        assert counts["all-reduce"] >= 1

    def test_async_pairs_not_double_counted(self):
        text = ("%ag = all-gather-start(...)\n"
                "%agd = all-gather-done(...)\n")
        assert collective_counts(text)["all-gather"] == 1


class TestCollectiveContract:
    def test_undeclared_extra_psum_produces_atp101(self):
        """Acceptance: an extra psum nothing declared -> its rule ID."""
        fn, x = _psum_program()
        contract = CollectiveContract(name="quiet_program", exhaustive=True)
        findings = contract.check(fn.lower(x).as_text())
        assert [f.rule for f in findings] == ["ATP101"]
        assert "all-reduce" in findings[0].message
        with pytest.raises(AnalysisViolation):
            contract.enforce(fn.lower(x).as_text())

    def test_exact_forbid_require_clauses(self):
        counts_text = "all-reduce\nall-gather\nall-gather\n"
        ok = CollectiveContract(
            name="ok", exact={"all-gather": 2},
            require=("all-reduce",), forbid=("collective-permute",))
        assert ok.check(counts_text) == []
        bad = CollectiveContract(name="bad", exact={"all-gather": 1})
        (f,) = bad.check(counts_text)
        assert "expected exactly 1, got 2" in f.message

    def test_require_group_accepts_alternatives(self):
        c = CollectiveContract(
            name="rs", require=(("reduce-scatter", "all-to-all"),))
        assert c.check("all-to-all\n") == []
        assert len(c.check("all-reduce\n")) == 1

    def test_non_exhaustive_ignores_undeclared(self):
        c = CollectiveContract(name="loose", require=("all-reduce",))
        assert c.check("all-reduce\ncollective-permute\n") == []

    def test_contract_table_resolves_by_name(self):
        ring = contract_for("ring_attention.forward")
        assert dict(ring.exact)["collective-permute"] == 2
        assert "all-gather" in ring.forbid
        with pytest.raises(KeyError):
            contract_for("no_such_program")


class TestTransferDetector:
    def test_pure_callback_in_jaxpr(self):
        def f(x):
            return jax.pure_callback(
                lambda v: np.asarray(v), jax.ShapeDtypeStruct((4,), jnp.float32), x)

        findings = find_host_transfers(jax.make_jaxpr(f)(jnp.ones(4)),
                                       name="cb_program")
        assert [f_.rule for f_ in findings] == ["ATP102"]
        assert "pure_callback" in findings[0].message

    def test_device_put_in_jaxpr(self):
        def f(x):
            return jax.device_put(x) * 2

        findings = find_host_transfers(jax.make_jaxpr(f)(jnp.ones(4)))
        assert any("device_put" in f_.message for f_ in findings)

    def test_clean_program(self):
        fn, x = _psum_program()
        assert find_host_transfers(jax.make_jaxpr(fn)(x)) == []

    def test_hlo_text_callback_targets(self):
        text = 'custom-call(...), custom_call_target="xla_python_cpu_callback"'
        (f,) = find_host_transfers(text, name="p")
        assert f.rule == "ATP102"


class TestReplicationAudit:
    def _mesh(self):
        return Mesh(np.array(jax.devices()).reshape(8), ("data",))

    def test_replicated_big_leaf_flags(self):
        mesh = self._mesh()
        rep = jax.device_put(np.zeros((512, 1024), np.float32),
                             NamedSharding(mesh, P()))  # 2 MiB replicated
        (f,) = audit_replication({"w": rep}, threshold_bytes=1 << 20)
        assert f.rule == "ATP103" and "'w'" in f.message

    def test_sharded_and_small_leaves_pass(self):
        mesh = self._mesh()
        sharded = jax.device_put(np.zeros((512, 1024), np.float32),
                                 NamedSharding(mesh, P("data")))
        small = jax.device_put(np.zeros((8,), np.float32),
                               NamedSharding(mesh, P()))
        assert audit_replication(
            {"w": sharded, "b": small}, threshold_bytes=1 << 20) == []

    def test_threshold_is_respected(self):
        mesh = self._mesh()
        rep = jax.device_put(np.zeros((512, 1024), np.float32),
                             NamedSharding(mesh, P()))
        assert audit_replication({"w": rep}, threshold_bytes=1 << 30) == []


# ---------------------------------------------------------------------------
# strict mode: Accelerator + serving engine
# ---------------------------------------------------------------------------


def _loss_fn(p, b):
    return jnp.mean((b["x"] @ p["w"]) ** 2)


def _dp_accelerator(strict):
    from accelerate_tpu import TrainState
    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.utils import MeshConfig

    acc = Accelerator(mesh_config=MeshConfig(axes={"data": 8}), strict=strict)
    ts = acc.prepare(TrainState.create(
        apply_fn=None, params={"w": np.ones((16, 16), np.float32)},
        tx=optax.sgd(1e-2)))
    loader = acc.prepare([{"x": np.ones((8, 16), np.float32)}])
    (batch,) = list(loader)
    return acc, ts, batch


class TestStrictMode:
    def test_error_mode_raises_at_trace_time_on_contract_violation(self):
        """Acceptance: strict='error' + a train step whose lowered
        collectives violate its declared contract -> AnalysisViolation
        before the program ever dispatches."""
        acc, ts, batch = _dp_accelerator("error")
        try:
            step = acc.train_step(_loss_fn, contract=CollectiveContract(
                name="dp_step", forbid=("all-reduce",)))  # DP MUST all-reduce
            with pytest.raises(AnalysisViolation, match="ATP101"):
                step(ts, batch)
            # a violating program raises on EVERY dispatch, not just #1
            with pytest.raises(AnalysisViolation):
                step(ts, batch)
        finally:
            acc.end_training()

    def test_error_mode_clean_contract_trains(self):
        acc, ts, batch = _dp_accelerator("error")
        try:
            step = acc.train_step(_loss_fn, contract=CollectiveContract(
                name="dp_step", require=("all-reduce",)))
            ts, m = step(ts, batch)
            assert bool(jax.device_get(jnp.isfinite(m["loss"])))
        finally:
            acc.end_training()

    def test_warn_mode_warns_and_counts_findings(self):
        acc, ts, batch = _dp_accelerator("warn")
        try:
            counter = acc.telemetry.counter(
                "analysis_findings_total", rule="ATP101")
            before = counter.value
            step = acc.train_step(_loss_fn, contract=CollectiveContract(
                name="dp_step", forbid=("all-reduce",)))
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                ts, _ = step(ts, batch)  # runs despite the finding
            assert any("ATP101" in str(x.message) for x in w)
            assert counter.value == before + 1
            # steady state: second call with the same layout never re-audits
            with warnings.catch_warnings(record=True) as w2:
                warnings.simplefilter("always")
                ts, _ = step(ts, batch)
            assert not any("ATP101" in str(x.message) for x in w2)
            assert counter.value == before + 1
        finally:
            acc.end_training()

    def test_error_mode_counts_findings_once_across_retries(self):
        """A caller that catches AnalysisViolation and retries must not
        inflate analysis_findings_total: the violation is cached per
        (layout, batch-sig) and re-raised without re-running the audit."""
        acc, ts, batch = _dp_accelerator("error")
        try:
            counter = acc.telemetry.counter(
                "analysis_findings_total", rule="ATP101")
            before = counter.value
            step = acc.train_step(_loss_fn, contract=CollectiveContract(
                name="dp_step", forbid=("all-reduce",)))
            for _ in range(3):
                with pytest.raises(AnalysisViolation):
                    step(ts, batch)
            assert counter.value == before + 1
        finally:
            acc.end_training()

    def test_batch_shape_drift_fallback_still_audits(self):
        """The identity-fast-path retry (batch shape drifts mid-loop, the
        stale AOT executable rejects the args) must route the NEW batch
        signature through the audit, not sidestep strict mode via the
        bare jit fallback."""
        from accelerate_tpu.data import make_global_batch

        acc, ts, batch = _dp_accelerator("warn")
        try:
            step = acc.train_step(_loss_fn, contract=CollectiveContract(
                name="dp_step", forbid=("all-reduce",)))
            with warnings.catch_warnings(record=True) as w1:
                warnings.simplefilter("always")
                ts, _ = step(ts, batch)  # audits signature A
            assert any("ATP101" in str(x.message) for x in w1)
            batch_b = make_global_batch(
                {"x": np.ones((16, 16), np.float32)}, acc.mesh)
            with warnings.catch_warnings(record=True) as w2:
                warnings.simplefilter("always")
                # ts is the previous output -> identity fast path -> the
                # signature-A executable rejects batch B -> fallback
                ts, _ = step(ts, batch_b)
            assert any("ATP101" in str(x.message) for x in w2), (
                "shape-drift fallback bypassed the strict audit")
        finally:
            acc.end_training()

    def test_transfer_guard_armed_and_restored(self):
        from accelerate_tpu.accelerator import Accelerator
        from accelerate_tpu.utils import MeshConfig

        prev = getattr(jax.config, "jax_transfer_guard_device_to_host",
                       "allow") or "allow"
        acc = Accelerator(mesh_config=MeshConfig(axes={"data": 8}),
                          strict="error")
        try:
            assert jax.config.jax_transfer_guard_device_to_host == "disallow"
        finally:
            acc.end_training()
        assert (getattr(jax.config, "jax_transfer_guard_device_to_host")
                or "allow") == prev

    def test_strict_rejects_bad_value(self):
        from accelerate_tpu.accelerator import Accelerator

        with pytest.raises(ValueError, match="strict"):
            Accelerator(strict="yes please")

    def test_strict_rejected_before_metrics_and_watchdog_start(self):
        """A bad strict value must not leak a bound metrics port or a live
        watchdog thread (same ordering guarantee as EngineConfig.strict)."""
        from accelerate_tpu.accelerator import Accelerator

        threads_before = {t.name for t in threading.enumerate()}
        with pytest.raises(ValueError, match="strict"):
            Accelerator(metrics_port=0, stall_timeout_s=60, strict="eror")
        leaked = {t.name for t in threading.enumerate()} - threads_before
        assert not leaked, f"failed init leaked threads: {leaked}"

    def test_warn_mode_replication_audit_flags_big_replicated_state(self):
        """The replication auditor reaches strict mode end to end: a DP
        state whose params exceed the (lowered) threshold is fully
        replicated by design and must be reported."""
        acc, ts, batch = _dp_accelerator("warn")
        try:
            step = acc.train_step(_loss_fn, replication_threshold=256)
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                step(ts, batch)
            assert any("ATP103" in str(x.message) for x in w)
        finally:
            acc.end_training()


class TestServingStrict:
    def _engine(self, **kw):
        from accelerate_tpu.models import gpt2
        from accelerate_tpu.serving.engine import Engine, EngineConfig

        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.init_params(cfg, jax.random.key(0))
        return Engine(gpt2, cfg, params, EngineConfig(
            num_slots=2, max_len=64, prefill_chunk=8, **kw))

    def test_default_contracts_pass_on_clean_engine(self):
        eng = self._engine(strict="error")
        try:
            req = eng.submit(np.arange(5), max_new_tokens=3)
            eng.run_until_idle()
            assert len(req.tokens) == 3
            # every program audited, all recorded clean (None)
            assert eng._audited == {
                "admit": None, "prefill": None, "decode": None}
            snap = eng.registry.snapshot()
            assert not any("analysis_findings" in k
                           for k in snap["counters"])
        finally:
            eng.close()

    def test_violating_contract_raises_in_error_mode(self):
        eng = self._engine(
            strict="error",
            contracts={"prefill": CollectiveContract(
                name="serving.prefill", require=("all-reduce",))})
        try:
            eng.submit(np.arange(5), max_new_tokens=2)
            with pytest.raises(AnalysisViolation, match="ATP101"):
                eng.run_until_idle()
        finally:
            eng.close()

    def test_invalid_strict_rejected_before_side_effects(self):
        """A bad strict value must raise BEFORE the metrics port binds or
        the watchdog thread starts — nothing to leak on a failed init."""
        import threading

        from accelerate_tpu.models import gpt2
        from accelerate_tpu.serving.engine import Engine, EngineConfig

        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.init_params(cfg, jax.random.key(0))
        threads_before = {t.name for t in threading.enumerate()}
        with pytest.raises(ValueError, match="strict"):
            Engine(gpt2, cfg, params, EngineConfig(
                num_slots=2, max_len=64, prefill_chunk=8,
                metrics_port=0, watchdog_timeout_s=60, strict="eror"))
        leaked = {t.name for t in threading.enumerate()} - threads_before
        assert not leaked, f"failed init leaked threads: {leaked}"

    def test_warn_mode_survives_audit_infrastructure_failure(self, monkeypatch):
        """strict='warn' promises 'warn and keep going': a crash in the
        audit machinery itself (not a finding) must not take down a
        serving step — same guarantee as the Accelerator's warn mode."""
        from accelerate_tpu.analysis import program as program_mod

        def boom(*a, **k):
            raise RuntimeError("audit infrastructure down")

        monkeypatch.setattr(program_mod, "find_host_transfers", boom)
        eng = self._engine(strict="warn")
        try:
            req = eng.submit(np.arange(5), max_new_tokens=3)
            eng.run_until_idle()
            assert len(req.tokens) == 3
        finally:
            eng.close()

    def test_error_mode_counts_findings_once_across_retries(self):
        eng = self._engine(
            strict="error",
            contracts={"prefill": CollectiveContract(
                name="serving.prefill", require=("all-reduce",))})
        try:
            eng.submit(np.arange(5), max_new_tokens=2)
            # every step() retries the same pending prefill: each attempt
            # re-raises the cached violation, the finding counts ONCE
            for _ in range(3):
                with pytest.raises(AnalysisViolation, match="ATP101"):
                    eng.step()
            snap = eng.registry.snapshot()
            assert snap["counters"][
                'analysis_findings_total{rule="ATP101"}'] == 1.0
        finally:
            eng.close()

    def test_mesh_placed_params_flagged(self):
        """'Params leaked onto a mesh': GSPMD inserts its collectives
        after the lowering the audit reads, so multi-device argument
        placement is caught directly at the placement."""
        from accelerate_tpu.models import gpt2
        from accelerate_tpu.serving.engine import Engine, EngineConfig

        cfg = gpt2.GPT2Config.tiny()
        params = gpt2.init_params(cfg, jax.random.key(0))
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        params = jax.device_put(
            params, NamedSharding(mesh, P()))  # replicated over 8 devices
        eng = Engine(gpt2, cfg, params, EngineConfig(
            num_slots=2, max_len=64, prefill_chunk=8, strict="error"))
        try:
            with pytest.raises(AnalysisViolation, match="devices"):
                eng.submit(np.arange(5), max_new_tokens=2)
                eng.run_until_idle()
        finally:
            eng.close()

    def test_violating_contract_warns_and_counts_in_warn_mode(self):
        eng = self._engine(
            strict="warn",
            contracts={"decode": CollectiveContract(
                name="serving.decode", require=("all-gather",))})
        try:
            req = eng.submit(np.arange(5), max_new_tokens=2)
            with warnings.catch_warnings(record=True) as w:
                warnings.simplefilter("always")
                eng.run_until_idle()
            assert any("ATP101" in str(x.message) for x in w)
            assert req.tokens  # engine kept serving
            snap = eng.registry.snapshot()
            assert snap["counters"][
                'analysis_findings_total{rule="ATP101"}'] == 1.0
        finally:
            eng.close()


class TestCheckpointSnapshotPair:
    """ISSUE 20: the stage/commit pair guarding the async-checkpoint
    manifest protocol is a declarative PAIRING_TABLE row — a staged
    snapshot that can leak past an exception path without commit() or
    rollback() is exactly the bug that publishes no manifest and strands
    a complete-on-disk checkpoint invisible."""

    def test_pair_is_registered(self):
        from accelerate_tpu.analysis.lifecycle import PAIRING_TABLE

        pair = next(p for p in PAIRING_TABLE
                    if p.name == "checkpoint-snapshot")
        assert pair.acquire == ("stage",)
        assert set(pair.release) == {"commit", "rollback"}
        assert pair.receivers == ("stager",)
        assert pair.returns_handle

    def test_staged_snapshot_leak_is_flagged(self):
        src = (
            "class Saver:\n"
            "    def save(self, output_dir, step):\n"
            "        pending = self.stager.stage(output_dir, step)\n"
            "        if step < 0:\n"
            "            return None\n"          # leaks the staged handle
            "        self.stager.commit(pending)\n"
        )
        findings = [f for f in lint_text(src, "t.py") if f.rule == "ATP201"]
        assert findings
        assert findings[0].data["resource"] == "checkpoint-snapshot"

    def test_rollback_on_error_path_is_clean(self):
        src = (
            "class Saver:\n"
            "    def save(self, output_dir, step):\n"
            "        pending = self.stager.stage(output_dir, step)\n"
            "        try:\n"
            "            self.write(pending)\n"
            "        except BaseException:\n"
            "            self.stager.rollback(pending)\n"
            "            raise\n"
            "        self.stager.commit(pending, deferred=True)\n"
        )
        assert not [f for f in lint_text(src, "t.py")
                    if f.rule == "ATP201"]

    def test_real_checkpointing_module_is_clean(self):
        """The production save path must pass its own guard rule."""
        path = os.path.join(REPO, "accelerate_tpu", "checkpointing.py")
        findings = lint_paths([path], root=REPO)
        assert not [f for f in findings if f.rule.startswith("ATP2")], \
            [(f.rule, f.line, f.data) for f in findings
             if f.rule.startswith("ATP2")]
