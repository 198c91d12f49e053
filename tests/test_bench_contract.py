"""The contract of bench.py: one JSON line, a parent that stays off JAX,
and a run that measures the chip or FAILS — no chip, a crashed or hung
child, an unknown device kind or a failed phase row all mean a non-zero
exit and no value under the TPU metric's name. The only CPU mode is the
explicit `--rehearse`, whose rows carry counts and the device's name."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_last_json_line_picks_the_line():
    bench = _load_bench()
    text = "WARNING: noise\n{\"a\": 1}\ntrailer\n{\"metric\": \"x\"}\n"
    assert bench._last_json_line(text) == '{"metric": "x"}'
    assert bench._last_json_line("no json at all") is None


def test_bench_child_env_contract():
    """The parent must spawn children with BENCH_CHILD=1 and never
    initialize JAX itself (jax must not be imported at module scope)."""
    src = open(os.path.join(ROOT, "bench.py")).read()
    assert "BENCH_CHILD" in src
    import re

    assert not re.search(r"^(import|from) (jax|accelerate_tpu)", src, re.M), \
        "a parent that has touched JAX holds the chip its children need"


def test_bench_parent_import_stays_off_jax():
    """Behavioural twin of the source check: importing bench.py (what the
    parent process does before it spawns children) loads no jax."""
    code = ("import importlib.util, sys; "
            "spec = importlib.util.spec_from_file_location('bench', "
            f"{os.path.join(ROOT, 'bench.py')!r}); "
            "m = importlib.util.module_from_spec(spec); "
            "spec.loader.exec_module(m); "
            "assert 'jax' not in sys.modules, 'parent imported jax'")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-500:]


def test_bench_without_chip_exits_nonzero_and_prints_no_value():
    """End to end, in a real child: on a machine whose JAX finds no TPU
    (this one: JAX_PLATFORMS=cpu) `python bench.py` exits NON-ZERO, still
    prints its one parseable line, and that line carries the cause and no
    value under the TPU metric's name — no CPU fallback row, no
    `cpu_smoke` number."""
    from accelerate_tpu.test_utils import checkout_child_env

    env = checkout_child_env({"JAX_PLATFORMS": "cpu"})
    env.pop("BENCH_CHILD", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode != 0, out.stdout
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    assert len(lines) == 1, out.stdout
    payload = json.loads(lines[0])
    assert payload["metric"] == "llama_train_tokens_per_sec_per_chip"
    assert "no tpu visible" in payload["error"]
    assert payload["value"] is None and payload["vs_baseline"] is None
    assert "extra" not in payload and "cpu_smoke" not in lines[0]


def test_serve_bench_smoke_emits_serving_metrics():
    """Tier-1-safe invocation of the offered-load serving harness: a
    miniature load in-process (no fresh-interpreter compile) must produce
    the serving JSON contract fields with a flat compile count."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(ROOT, "benchmarks", "serve_bench.py"))
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    engine, cfg = sb.build_tiny_engine(
        "gpt2", num_slots=2, max_len=32, prefill_chunk=8)
    summary = sb.run_offered_load(
        engine, cfg.vocab_size, num_requests=4, rate_hz=500.0,
        prompt_len=(2, 6), max_new_tokens=(2, 4))
    assert summary["requests_finished"] == 4
    assert summary["tokens_per_sec"] > 0
    assert summary["ttft_p50_ms"] > 0
    assert summary["per_token_p50_ms"] > 0
    assert summary["compiles_decode"] == 1
    # the ISSUE 11 acceptance smoke: decode MFU / MXU-idle / goodput
    # non-null on CPU (nominal peaks — labeled, but the pipeline flows),
    # with the compile count still flat (sampling is host-side)
    for key in ("decode_mfu", "decode_mxu_idle_fraction", "goodput",
                "decode_device_time_mean_ms"):
        assert key in summary and summary[key] == summary[key], key
    assert 0.0 < summary["goodput"] <= 1.0


def test_bench_serving_row_shape():
    """bench.py's serving row reports the offered-load fields and can
    never poison the one-line contract (errors fold into the row)."""
    bench = _load_bench()
    row = bench._serving_row()
    assert row["requests_finished"] == 12
    for field in ("tokens_per_sec", "ttft_p50_ms", "ttft_p99_ms",
                  "per_token_p50_ms", "per_token_p99_ms"):
        assert row[field] > 0, row
    # roofline/goodput fields (ISSUE 11) ride the same row
    for field in ("decode_mfu", "decode_mxu_idle_fraction", "goodput"):
        assert field in row and row[field] == row[field], (field, row)


def test_bench_serving_prefix_row_shape():
    """The shared-prefix row (ISSUE 5): hit rate and cached-token
    fraction next to the latency percentiles — a reuse regression shows
    up as prefix_hit_rate 0 in the bench line. Tiny parameters keep this
    tier-1-safe."""
    bench = _load_bench()
    row = bench._serving_prefix_row(num_requests=6, prefix_pool=2,
                                    prefix_len=16, page_size=8)
    assert row["requests_finished"] == 6
    assert row["prefix_hit_rate"] > 0
    assert row["cached_token_fraction"] > 0
    assert row["prefill_chunks"] > 0
    assert row["tokens_per_sec"] > 0


def test_rehearse_is_explicit_and_marks_every_child(monkeypatch, capsys):
    """The one CPU mode is asked for by name: `--rehearse` refuses to run
    without the JAX_PLATFORMS=cpu pin (exit 2, no child spawned), and with
    it every child — train and each phase, `pod_dist` included — is told
    it is a rehearsal; the headline stays "skipped", never a value."""
    bench = _load_bench()
    calls = []

    class FakeOut:
        returncode = 0
        stderr = ""
        stdout = json.dumps({
            "metric": "bench_rehearsal", "unit": "none",
            "device": {"platform": "cpu", "kind": "cpu", "count": 1},
            "skipped": "rehearsal on cpu"}) + "\n"

    def fake_run(cmd, env=None, **kw):
        calls.append(env)
        return FakeOut()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("BENCH_CHILD", raising=False)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    assert bench.main(["--rehearse"]) == 2 and not calls
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.main(["--rehearse"]) == 0
    phases = [e.get("BENCH_PHASE") for e in calls]
    assert phases == ["train", "serving", "serving_prefix", "server", "pod",
                      "serving_spec", "serving_host_tier", "pod_dist"]
    assert all(e["BENCH_REHEARSE"] == "1" for e in calls)
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "skipped" in line and line.get("value") is None
    assert line["device"]["platform"] == "cpu"


def test_rehearsal_rows_drop_every_rate_time_and_utilization():
    """What the rehearsal prints names no device metric: `_counts_only`
    keeps counters and verdicts and drops rates, times, utilizations."""
    bench = _load_bench()
    row = bench._counts_only({
        "tokens_per_sec": 9.0, "ttft_p50_ms": 1.0, "per_token_p99_ms": 2.0,
        "decode_mfu": 0.1, "decode_mxu_idle_fraction": 0.9,
        "decode_hbm_bw_util": 0.2, "goodput": 0.5, "wall_s": 3.0,
        "requests_finished": 12.0, "compiles_decode": 1.0,
        "paged_attention": "dense",
        "baseline": {"tokens_per_sec": 1.0, "prefill_chunks": 7.0},
        "greedy_byte_identical": True})
    assert row == {"requests_finished": 12.0, "compiles_decode": 1.0,
                   "paged_attention": "dense",
                   "baseline": {"prefill_chunks": 7.0},
                   "greedy_byte_identical": True}


def test_hung_phase_is_isolated_to_its_row(monkeypatch, capsys):
    """A wedged device during an extra-row phase costs that phase's row —
    it carries "error", the train numbers and the one-line contract
    survive — and the exit code says the run failed. Stubbed: the train
    child succeeds, every phase child 'hangs' (TimeoutExpired)."""
    bench = _load_bench()

    class FakeOut:
        returncode = 0
        stderr = ""
        stdout = json.dumps({
            "metric": "llama_train_tokens_per_sec_per_chip",
            "value": 123.0, "vs_baseline": 1.0, "unit": "tokens/s/chip",
            "extra": {"mfu": 0.5}}) + "\n"

    def fake_run(cmd, env=None, timeout=None, **kw):
        if env.get("BENCH_PHASE") != "train":
            raise bench.subprocess.TimeoutExpired(cmd, timeout)
        return FakeOut()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("BENCH_CHILD", raising=False)
    assert bench.main([]) == 1             # a failed row fails the run
    line = json.loads(capsys.readouterr().out.strip())
    assert line["value"] == 123.0          # the headline survived
    assert line.get("error") is None       # ... unpoisoned
    assert "hung" in line["extra"]["serving"]["error"]
    assert "hung" in line["extra"]["serving_prefix"]["error"]
    assert "hung" in line["extra"]["server"]["error"]


class _TrainOk:
    returncode = 0
    stderr = ""
    stdout = json.dumps({
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": 123.0, "vs_baseline": 1.0, "unit": "tokens/s/chip",
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "extra": {"mfu": 0.5}}) + "\n"


class _NoTpu:
    returncode = 3  # what a child that finds no TPU exits with
    stderr = "no tpu visible: jax reports {'platform': 'cpu'}\n"
    stdout = ""


def test_phase_without_chip_fails_the_run(monkeypatch, capsys):
    """A phase child that finds no TPU exits 3; the parent reports it in
    the row's error and exits non-zero — it never attaches another
    backend's serving numbers under a TPU headline. `pod_dist` is not a
    phase of the chip run at all (one process per chip)."""
    bench = _load_bench()

    def fake_run(cmd, env=None, timeout=None, **kw):
        return _TrainOk() if env.get("BENCH_PHASE") == "train" else _NoTpu()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("BENCH_CHILD", raising=False)
    assert bench.main([]) == 1
    line = json.loads(capsys.readouterr().out.strip())
    assert line["value"] == 123.0
    for row in ("serving", "serving_prefix", "server", "pod",
                "serving_spec", "serving_host_tier"):
        assert "no tpu visible" in line["extra"][row]["error"]
    assert "pod_dist" not in line["extra"]


def test_no_chip_is_not_retried_and_never_falls_back_to_cpu(monkeypatch,
                                                            capsys):
    """The retry-with-backoff loop and the CPU fallback are gone: a train
    child that finds no TPU is spawned ONCE, no child is ever started with
    a CPU pin of the bench's own making, the line has no value, exit 1."""
    bench = _load_bench()
    envs = []

    def fake_run(cmd, env=None, timeout=None, **kw):
        envs.append(env)
        return _NoTpu()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("BENCH_CHILD", raising=False)
    assert bench.main([]) == 1
    assert [e["BENCH_PHASE"] for e in envs] == ["train"]
    assert all(e.get("JAX_PLATFORMS") != "cpu" for e in envs)
    assert all(e.get("BENCH_REHEARSE") != "1" for e in envs)
    line = json.loads(capsys.readouterr().out.strip())
    assert line["value"] is None and "no tpu visible" in line["error"]
    assert "extra" not in line  # no phase ran, no cpu_smoke reading


def test_crashed_or_hung_train_child_exits_nonzero_with_the_cause(
        monkeypatch, capsys):
    bench = _load_bench()

    class Crash:
        returncode = 1
        stderr = "Traceback ...\nValueError: no peak FLOP/s known for " \
                 "device kind 'TPU v99'\n"
        stdout = ""

    monkeypatch.setattr(bench.subprocess, "run",
                        lambda cmd, env=None, **kw: Crash())
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("BENCH_CHILD", raising=False)
    assert bench.main([]) == 1
    line = json.loads(capsys.readouterr().out.strip())
    assert line["value"] is None
    assert "no peak FLOP/s known" in line["error"]

    def hang(cmd, env=None, timeout=None, **kw):
        raise bench.subprocess.TimeoutExpired(cmd, timeout)

    monkeypatch.setattr(bench.subprocess, "run", hang)
    assert bench.main([]) == 1
    line = json.loads(capsys.readouterr().out.strip())
    assert line["value"] is None and "hung" in line["error"]


def test_unknown_tpu_device_kind_is_an_error_not_197e12(monkeypatch):
    """run_bench on a TPU whose device_kind is not in the peak table
    raises before any work; it used to assume a v5e's 197e12. A non-TPU
    device raises NoChip unless the rehearsal was asked for."""
    from accelerate_tpu.ops import kernel_mode

    bench = _load_bench()
    monkeypatch.setattr(bench, "_device_row", lambda: {
        "platform": "tpu", "kind": "TPU v99 imaginary", "count": 1})
    # run_bench on a real TPU switches the process to compiled-kernels-only;
    # it must not get that far here, and must not leak it to later tests
    monkeypatch.setattr(kernel_mode, "_require_compiled", False)
    with pytest.raises(ValueError, match="no peak FLOP/s known"):
        bench.run_bench()
    assert kernel_mode._require_compiled is False
    monkeypatch.setattr(bench, "_device_row", lambda: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    with pytest.raises(bench.NoChip, match="no tpu visible"):
        bench.run_bench()
    assert "197e12" not in open(os.path.join(ROOT, "bench.py")).read()


def test_serve_dry_run_smoke_in_process():
    """ISSUE 7 satellite (the PR 4 __main__-guard lesson): the CLI
    entrypoint `accelerate-tpu serve --dry-run` must build the full
    config in-process, print one JSON line, and exit 0 — so a broken
    entrypoint can never ship silently."""
    from accelerate_tpu.commands.accelerate_cli import main

    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(["serve", "--dry-run", "--family", "gpt2",
                   "--tenants", "gold:priority=0,weight=4,slo=0.25;"
                   "bronze:priority=1"])
    assert rc == 0
    payload = json.loads(buf.getvalue().strip())
    assert payload["dry_run"] is True
    assert "/v1/completions" in payload["routes"]
    assert "gold" in payload["tenants"]
    # a bad tenant spec must fail loudly, not serve a typo
    assert main(["serve", "--dry-run", "--tenants", "x:weight=0"]) == 2
    assert main(["serve", "--dry-run", "--tenants", "x:bogus=1"]) == 2


def test_bench_server_row_shape():
    """bench.py's extra.server row: the two-tenant HTTP phase reports
    per-tier Prometheus-sourced numbers and the flat compile count."""
    bench = _load_bench()
    row = bench._server_row(num_requests=6)
    assert row["compiles_decode"] == 1.0
    assert row["tenants.gold.sent"] == 3
    assert row["tenants.bronze.sent"] == 3
    assert "tenants.gold.slo_attainment" in row
    assert row["tokens_per_sec"] > 0


def test_schema_v2_row_normalizer():
    """ISSUE 8 satellite: every row carries non-null metric/unit plus
    exactly one non-null of value/error/skipped — including rows that
    arrive with none or several."""
    bench = _load_bench()
    row = bench._normalize_row({}, "m", "u")
    assert row["metric"] == "m" and row["unit"] == "u"
    assert row["error"]  # nothing produced parses as failure
    row = bench._normalize_row({"metric": None, "unit": None,
                                "value": 1.0}, "m", "u")
    assert row["metric"] == "m" and row["unit"] == "u"
    assert row["value"] == 1.0 and row.get("error") is None
    # error wins over a suspect value
    row = bench._normalize_row({"value": 2.0, "error": "boom"}, "m", "u")
    assert row["error"] == "boom" and row["value"] is None
    # a skipped (operator pin) row stays skipped, not error
    row = bench._normalize_row({"skipped": "pin", "value": None}, "m", "u")
    assert row["skipped"] == "pin" and "error" not in row


def _assert_schema_v2(line: dict):
    assert line["schema_version"] == 3
    rows = [line] + [line["extra"][k]
                     for k in ("serving", "serving_prefix", "server", "pod",
                               "pod_dist", "serving_spec", "serving_host_tier")
                     if k in line.get("extra", {})]
    for row in rows:
        assert row.get("metric"), row
        assert row.get("unit"), row
        populated = [k for k in ("value", "error", "skipped")
                     if row.get(k) is not None]
        assert len(populated) == 1, (populated, row)


def test_emitted_line_meets_schema_v2(monkeypatch, capsys):
    """Both the success and the all-phases-hung shapes satisfy the v2
    row contract end to end (stubbed children, real _emit path)."""
    bench = _load_bench()

    class TrainOut:
        returncode = 0
        stderr = ""
        stdout = json.dumps({
            "metric": "llama_train_tokens_per_sec_per_chip",
            "value": 123.0, "vs_baseline": 1.0, "unit": "tokens/s/chip",
            "extra": {"mfu": 0.5}}) + "\n"

    class PhaseOut:
        returncode = 0
        stderr = ""
        stdout = json.dumps({"tokens_per_sec": 9.0}) + "\n"

    def fake_run(cmd, env=None, timeout=None, **kw):
        return TrainOut() if env.get("BENCH_PHASE") == "train" \
            else PhaseOut()

    monkeypatch.setattr(bench.subprocess, "run", fake_run)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv("BENCH_CHILD", raising=False)
    monkeypatch.setenv("BENCH_SERVING", "1")
    assert bench.main([]) == 0
    line = json.loads(capsys.readouterr().out.strip())
    _assert_schema_v2(line)
    assert line["extra"]["serving"]["value"]["tokens_per_sec"] == 9.0
    assert line["extra"]["serving"]["metric"] == "serving_offered_load"

    def hung_run(cmd, env=None, timeout=None, **kw):
        if env.get("BENCH_PHASE") != "train":
            raise bench.subprocess.TimeoutExpired(cmd, timeout)
        return TrainOut()

    monkeypatch.setattr(bench.subprocess, "run", hung_run)
    assert bench.main([]) == 1
    line = json.loads(capsys.readouterr().out.strip())
    _assert_schema_v2(line)
    assert "hung" in line["extra"]["server"]["error"]
    assert "hung" in line["extra"]["pod"]["error"]


def test_debug_requests_and_incident_bundle_in_process(tmp_path):
    """ISSUE 8 satellite: in-process smoke through the REAL stack — hit
    /debug/requests on the live HTTP door, then force a watchdog stall
    whose incident bundle (with the engine's dumps) lands in a tmpdir
    and renders through the incident CLI."""
    import asyncio
    import importlib.util

    from accelerate_tpu.commands.accelerate_cli import main as cli_main
    from accelerate_tpu.server.config import ServerConfig
    from accelerate_tpu.server.http import HttpFrontDoor
    from accelerate_tpu.server.service import InferenceService
    from accelerate_tpu.server.tokenizer import get_tokenizer
    from accelerate_tpu.telemetry.watchdog import StallWatchdog

    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(ROOT, "benchmarks", "serve_bench.py"))
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    engine, cfg = sb.build_tiny_engine("gpt2", num_slots=2, max_len=32,
                                       prefill_chunk=8)
    service = InferenceService(
        engine, get_tokenizer("auto", cfg.vocab_size),
        ServerConfig(port=0, debug_endpoints=True))
    door = HttpFrontDoor(service)

    async def scenario():
        await door.start()
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", door.port)
            writer.write(b"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                         b"Content-Length: 47\r\n\r\n"
                         b'{"prompt": [1,2,3], "max_tokens": 2, "n": 1 }  ')
            await writer.drain()
            resp = await reader.read()
            writer.close()
            assert b" 200 " in resp.split(b"\r\n", 1)[0], resp[:200]
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", door.port)
            writer.write(b"GET /debug/requests HTTP/1.1\r\nHost: t\r\n"
                         b"Content-Length: 0\r\n\r\n")
            await writer.drain()
            resp = await reader.read()
            writer.close()
            head, _, body = resp.partition(b"\r\n\r\n")
            assert b" 200 " in head
            dbg = json.loads(body)
            assert dbg["queued"] == [] and dbg["running"] == []
            assert dbg["service"]["healthy"] is True
        finally:
            await door.stop()

    asyncio.run(asyncio.wait_for(scenario(), 120))

    # force a stall: fake clock, bundle into the tmpdir
    now = [0.0]
    wd = StallWatchdog(5.0, clock=lambda: now[0],
                       incident_dir=str(tmp_path),
                       registry=engine.registry,
                       dumps=engine.incident_dumps)
    now[0] = 9.0
    report = wd.check()
    assert report is not None and "bundle_path" in report
    bundle = report["bundle_path"]
    names = set(os.listdir(bundle))
    assert {"manifest.json", "report.json", "stacks.txt", "trace.json",
            "metrics.json", "scheduler.json"} <= names
    assert cli_main(["incident", "show", os.path.basename(bundle),
                     "--dir", str(tmp_path)]) == 0


# ---------------------------------------------------------------------------
# pod phase (ISSUE 9)
# ---------------------------------------------------------------------------


def _load_serve_bench():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(ROOT, "benchmarks", "serve_bench.py"))
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    return sb


def test_serve_bench_pod_roles_parse():
    sb = _load_serve_bench()
    assert sb.parse_pod_roles("prefill=2,decode=3") == (2, 3)
    assert sb.parse_pod_roles("decode=1,prefill=1") == (1, 1)
    with pytest.raises(ValueError, match="BOTH roles"):
        sb.parse_pod_roles("prefill=2")
    with pytest.raises(ValueError, match="bad --pod-roles"):
        sb.parse_pod_roles("prefill=2,decode=x")
    with pytest.raises(ValueError, match="twice"):
        sb.parse_pod_roles("prefill=1,decode=2,decode=8")


def test_serve_bench_pod_mode_smoke():
    """The offered-load harness drives a disaggregated pod through the
    same submit/step surface: miniature in-process load, shipment
    counters populated, per-role compile counts flat."""
    sb = _load_serve_bench()
    engine, cfg, _ = sb.build_tiny_pod(
        "gpt2", pod_roles=(1, 1), num_slots=2, max_len=32, prefill_chunk=8)
    summary = sb.run_offered_load(
        engine, cfg.vocab_size, num_requests=4, rate_hz=500.0,
        prompt_len=(2, 6), max_new_tokens=(2, 4))
    assert summary["requests_finished"] == 4
    assert summary["tokens_per_sec"] > 0
    assert summary["pod_shipments"] > 0
    assert summary["pod_pages_shipped"] > 0
    assert summary["compiles_decode"] == 1
    assert summary["compiles_install"] == 1


def test_bench_pod_row_shape():
    """bench.py's failure-isolated extra.pod phase row: shipment
    counters and per-role compiles next to the latency percentiles."""
    bench = _load_bench()
    row = bench._pod_row(num_requests=5)
    assert row["requests_finished"] == 5
    assert row["pod_shipments"] > 0
    assert row["pod_pages_shipped"] >= row["pod_shipments"]
    assert row["compiles_decode"] == 1
    assert row["compiles_install"] == 1
    assert row["tokens_per_sec"] > 0


def test_bench_serving_row_names_kernel_and_kv_dtype():
    """ISSUE 10: the extra.serving row carries which decode attention op
    and KV dtype produced the numbers (plus the kv-bytes/capacity pair),
    so BENCH_r* lines are comparable across configs."""
    bench = _load_bench()
    row = bench._serving_row()
    assert row["paged_attention"] in ("kernel", "dense")
    assert row["kv_dtype"] in ("int8", "bfloat16", "float32")
    assert row["pages_capacity"] > 0
    assert "kv_bytes_in_use" in row


def test_serve_bench_kv_dtype_and_paged_attention_flags():
    """The --kv-dtype/--no-paged-attention A/B axes reach the engine:
    int8 halves kv_bytes_in_use per page (same page count on the same
    seeded load) and the summary reports the capacity fields."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(ROOT, "benchmarks", "serve_bench.py"))
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    out = {}
    for kvd in (None, "int8"):
        engine, cfg = sb.build_tiny_engine(
            "gpt2", num_slots=2, max_len=32, prefill_chunk=8,
            kv_dtype=kvd, paged_attention=False)
        assert engine._use_paged_kernel is False
        summary = sb.run_offered_load(
            engine, cfg.vocab_size, num_requests=3, rate_hz=500.0,
            prompt_len=(2, 6), max_new_tokens=(2, 3))
        assert summary["requests_finished"] == 3
        assert summary["pages_capacity"] == engine.cache.num_pages
        out[kvd] = engine.cache.page_nbytes
    # code bytes halve; the per-row scales add the documented 2/D
    ratio = out["int8"] / out[None]
    assert 0.5 < ratio <= 0.6, out


def test_serve_bench_speculative_flag_smoke():
    """The --speculative/--draft-k A/B axis reaches the engine
    (ISSUE 12): the self-draft run reports the speculation summary keys
    — accept rate 1.0 (identical draft), tokens_per_decode_step above
    the acceptance bar (> 1.5 at k=3), five flat compile counts."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_bench", os.path.join(ROOT, "benchmarks", "serve_bench.py"))
    sb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sb)
    engine, cfg = sb.build_tiny_engine(
        "gpt2", num_slots=2, max_len=32, prefill_chunk=8,
        speculative=True, draft_k=3)
    summary = sb.run_offered_load(
        engine, cfg.vocab_size, num_requests=4, rate_hz=500.0,
        prompt_len=(2, 6), max_new_tokens=(4, 6))
    assert summary["requests_finished"] == 4
    assert summary["spec_accept_rate"] == 1.0
    assert summary["tokens_per_decode_step"] > 1.5
    assert summary["spec_drafted_tokens"] == summary["spec_accepted_tokens"]
    for prog in ("admit", "prefill", "draft_prefill", "draft", "verify"):
        assert summary[f"compiles_{prog}"] == 1, prog
    # the decode-role roofline keys read the VERIFY program
    assert "decode_mxu_idle_fraction" in summary


# ---------------------------------------------------------------------------
# device-cost attribution & the bench regression gate (ISSUE 11)
# ---------------------------------------------------------------------------


def _write_row(tmp_path, name: str, row: dict) -> str:
    path = os.path.join(str(tmp_path), name)
    with open(path, "w") as f:
        json.dump(row, f)
    return path


def _baseline_capture(tmp_path) -> str:
    """A bench line in the driver's capture wrapper (the row under
    "parsed"). The values are SYNTHETIC test data in the shape of a chip
    row, not a measurement of anything."""
    return _write_row(tmp_path, "baseline_capture.json", {
        "n": 1, "rc": 0, "parsed": {
            "schema_version": 3,
            "metric": "llama_train_tokens_per_sec_per_chip",
            "value": 1000.0, "unit": "tokens/s/chip", "vs_baseline": 1.25,
            "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
            "extra": {"mfu": 0.5, "params": 400332288, "batch": 8,
                      "seq": 2048, "steps": 20, "wall_s": 8.0,
                      "n_chips": 1}}})


def test_bench_diff_exit_codes(tmp_path):
    """The regression gate's three verdicts, driven on a bench row in the
    driver's capture wrapper: identical rows pass (0), a degraded copy
    exits 1, a contract-violating row exits 2."""
    from accelerate_tpu.commands.bench_diff import load_row, main

    real = _baseline_capture(tmp_path)
    assert main([real, real]) == 0

    row = load_row(real)
    bad = json.loads(json.dumps(row))
    bad["value"] = row["value"] * 0.8           # tokens/s fell 20%
    bad["extra"]["mfu"] = row["extra"]["mfu"] * 0.7
    degraded = _write_row(tmp_path, "degraded.json", bad)
    assert main([real, degraded]) == 1
    # generous tolerance waves the same drop through
    assert main([real, degraded, "--tolerance", "0.5"]) == 0
    # per-metric override: only the mfu drop is out of tolerance
    assert main([real, degraded, "--tolerance", "0.5",
                 "--metric-tolerance", "mfu=0.1"]) == 1

    # vs_baseline is a compared top-level metric, not a dead table entry
    vb = json.loads(json.dumps(row))
    vb["vs_baseline"] = row["vs_baseline"] * 0.5
    assert main([real, _write_row(tmp_path, "vb.json", vb)]) == 1

    malformed = _write_row(tmp_path, "malformed.json", {"value": 3})
    assert main([real, malformed]) == 2
    assert main([real, os.path.join(str(tmp_path), "missing.json")]) == 2


def test_bench_diff_headline_value_to_error_regresses(tmp_path):
    """Losing the number IS a regression: a baseline with a real value
    against a candidate whose headline carries an error must fail the
    gate (exit 1, 'degraded' in the report) — and a deliberate operator
    skip must NOT."""
    from accelerate_tpu.commands.bench_diff import (
        compare_rows, load_row, main)

    real = _baseline_capture(tmp_path)
    err_row = {"schema_version": 2,
               "metric": "llama_train_tokens_per_sec_per_chip",
               "unit": "tokens/s/chip", "value": None,
               "error": "no tpu visible", "extra": {}}
    err = _write_row(tmp_path, "err.json", err_row)
    assert main([real, err]) == 1
    report = compare_rows(load_row(real), err_row)
    assert report["degraded"]
    skip_row = dict(err_row, error=None, skipped="operator cpu pin")
    skipped = _write_row(tmp_path, "skip.json", skip_row)
    assert main([real, skipped]) == 0


def test_bench_diff_phase_row_regression(tmp_path):
    """Schema-v2 phase rows compare their value dicts with direction
    awareness: ttft_p99_ms RISING is the regression; tokens_per_sec
    rising is an improvement."""
    from accelerate_tpu.commands.bench_diff import compare_rows

    def line(ttft, tps):
        return {
            "schema_version": 2, "metric": "m", "unit": "u", "value": 1.0,
            "extra": {"serving": {
                "metric": "serving_offered_load", "unit": "summary",
                "value": {"ttft_p99_ms": ttft, "tokens_per_sec": tps,
                          "wall_s": 3.0}}},
        }

    report = compare_rows(line(10.0, 100.0), line(20.0, 150.0))
    keys = {e["key"] for e in report["regressions"]}
    assert keys == {"extra.serving.ttft_p99_ms"}
    assert {e["key"] for e in report["improvements"]} == {
        "extra.serving.tokens_per_sec"}
    # wall_s has no direction: configuration, never compared
    assert not any("wall_s" in e["key"]
                   for e in report["regressions"] + report["improvements"])
    # a phase that went value -> error is a degraded row
    broken = line(10.0, 100.0)
    broken["extra"]["serving"] = {"metric": "serving_offered_load",
                                  "unit": "summary",
                                  "error": "phase hung"}
    report = compare_rows(line(10.0, 100.0), broken)
    assert report["degraded"] == [
        "extra.serving (phase went value -> error)"]


def test_bench_diff_serving_spec_row_compares(tmp_path):
    """ISSUE 12: the extra.serving_spec A/B row runs through bench-diff
    with direction awareness — a drop in the speculative arm's
    tokens_per_decode_step (or accept rate) is a regression; the
    draft_k config scalar and the exactness verdict are never
    compared."""
    from accelerate_tpu.commands.bench_diff import compare_rows

    def line(tps_step, accept):
        return {
            "schema_version": 2, "metric": "m", "unit": "u", "value": 1.0,
            "extra": {"serving_spec": {
                "metric": "serving_speculative_ab", "unit": "summary",
                "value": {"draft_k": 4, "greedy_byte_identical": True,
                          "baseline": {"tokens_per_decode_step": 2.0},
                          "speculative": {"tokens_per_decode_step": tps_step,
                                          "spec_accept_rate": accept}}}},
        }

    report = compare_rows(line(7.5, 1.0), line(1.1, 0.2))
    keys = {e["key"] for e in report["regressions"]}
    assert keys == {
        "extra.serving_spec.speculative.tokens_per_decode_step",
        "extra.serving_spec.speculative.spec_accept_rate"}
    assert not any("draft_k" in e["key"] or "byte_identical" in e["key"]
                   for e in report["regressions"] + report["improvements"])
    assert not compare_rows(line(7.5, 1.0), line(7.5, 1.0))["regressions"]


def test_regression_script_delegates(tmp_path):
    """benchmarks/regression.py is the script form of the same gate:
    same exit codes from a bare checkout."""
    real = _baseline_capture(tmp_path)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "regression.py"),
         real, real], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    malformed = _write_row(tmp_path, "bad.json", {"value": 1})
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "regression.py"),
         real, malformed], capture_output=True, text=True, timeout=60)
    assert out.returncode == 2, (out.stdout, out.stderr)


def test_debug_profile_gating_and_capture(tmp_path):
    """/debug/profile: 404 for EVERY method when the debug gate is off
    (indistinguishable from unknown paths), a real jax.profiler capture
    when on — the response names the logdir and the trace files exist;
    bad durations answer 400."""
    import asyncio

    from accelerate_tpu.server.config import ServerConfig
    from accelerate_tpu.server.http import HttpFrontDoor
    from accelerate_tpu.server.service import InferenceService
    from accelerate_tpu.server.tokenizer import get_tokenizer

    sb = _load_serve_bench()
    engine, cfg = sb.build_tiny_engine("gpt2", num_slots=2, max_len=32,
                                       prefill_chunk=8)

    async def req(port: int, method: str, target: str) -> tuple[int, bytes]:
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(f"{method} {target} HTTP/1.1\r\nHost: t\r\n"
                     "Content-Length: 0\r\n\r\n".encode())
        await writer.drain()
        resp = await reader.read()
        writer.close()
        head, _, body = resp.partition(b"\r\n\r\n")
        return int(head.split(b" ")[1]), body

    async def gated_off():
        service = InferenceService(
            engine, get_tokenizer("auto", cfg.vocab_size),
            ServerConfig(port=0, debug_endpoints=False))
        door = HttpFrontDoor(service)
        await door.start()
        try:
            for method in ("GET", "POST", "HEAD"):
                status, _ = await req(door.port, method,
                                      "/debug/profile?duration_s=0.01")
                assert status == 404, method
        finally:
            await door.stop()

    asyncio.run(asyncio.wait_for(gated_off(), 60))

    logdir = os.path.join(str(tmp_path), "capture")

    async def gated_on():
        service = InferenceService(
            engine, get_tokenizer("auto", cfg.vocab_size),
            ServerConfig(port=0, debug_endpoints=True))
        door = HttpFrontDoor(service)
        await door.start()
        try:
            status, body = await req(
                door.port, "GET", "/debug/profile?duration_s=bogus")
            assert status == 400
            status, body = await req(
                door.port, "GET", "/debug/profile?duration_s=99")
            assert status == 400
            # HEAD must NOT start a capture (the one side-effecting
            # debug route): 405, never GET-minus-body
            status, _ = await req(door.port, "HEAD",
                                  "/debug/profile?duration_s=30")
            assert status == 405
            status, body = await req(
                door.port, "GET",
                f"/debug/profile?duration_s=0.05&logdir={logdir}")
            assert status == 200, body
            payload = json.loads(body)["profile"]
            assert payload["logdir"] == logdir
        finally:
            await door.stop()

    asyncio.run(asyncio.wait_for(gated_on(), 120))
    produced = [os.path.join(dirpath, f)
                for dirpath, _, files in os.walk(logdir) for f in files]
    assert produced, "profiler capture produced no trace files"


# ---------------------------------------------------------------------------
# resilient training in the bench line (ISSUE 20)
# ---------------------------------------------------------------------------


def test_bench_diff_resilience_directions():
    """The goodput/drain/resume keys the resilient smoke adds to
    extra.goodput must carry direction entries so bench-diff gates them:
    goodput up is better, drain and resume latency down is better."""
    from accelerate_tpu.commands.bench_diff import metric_direction

    assert metric_direction("extra.goodput.goodput") == 1
    assert metric_direction("extra.goodput.resilient") == 1
    assert metric_direction("extra.goodput.checkpoint_drain_p99_s") == -1
    assert metric_direction("extra.goodput.checkpoint_drain_mean_s") == -1
    assert metric_direction("extra.goodput.resume_latency_s") == -1
    # attempt/resume counts are run facts, not compared metrics
    assert metric_direction("extra.goodput.attempts") == 0
    assert metric_direction("extra.goodput.resumes") == 0


def test_bench_diff_flags_goodput_regression():
    from accelerate_tpu.commands.bench_diff import compare_rows

    def line(resilient, drain):
        return {"schema_version": 2, "metric": "m", "unit": "u",
                "value": 1.0,
                "extra": {"goodput": {"resilient": resilient,
                                      "checkpoint_drain_p99_s": drain,
                                      "attempts": 1}}}

    report = compare_rows(line(0.95, 0.05), line(0.60, 0.50))
    keys = {e["key"] for e in report["regressions"]}
    assert "extra.goodput.resilient" in keys
    assert "extra.goodput.checkpoint_drain_p99_s" in keys
    assert not compare_rows(line(0.95, 0.05), line(0.95, 0.05))["regressions"]


def test_bench_resilience_smoke_row(tmp_path, monkeypatch):
    """The in-bench resilient smoke: run_resilient over a toy step must
    produce the extra.goodput keys the trajectory tooling reads, with the
    compile-counter deltas flat."""
    import jax
    import jax.numpy as jnp
    import optax

    from accelerate_tpu.accelerator import Accelerator
    from accelerate_tpu.training import TrainState

    bench = _load_bench()
    acc = Accelerator()
    ts = TrainState.create(apply_fn=None, params={"w": jnp.zeros((8, 8))},
                           tx=optax.sgd(1e-2))

    @jax.jit
    def step(state, batch):
        grads = jax.tree_util.tree_map(jnp.ones_like, state.params)
        return state.apply_gradients(grads), {"loss": jnp.float32(0.0)}

    row = bench._resilience_smoke(acc, step, ts, {"x": 0}, steps=6)
    assert "attempts" not in row  # the retry loop is gone
    assert 0.0 <= row["resilient"] <= 1.0
    assert row["saves"] >= 2 and row["resumes"] == 0
    assert row["train_pin_computations"] == 0
    assert row["train_aot_compiles"] == 0
    assert row["checkpoint_drain_p99_s"] >= 0.0
    assert row["checkpoint_stage_mean_s"] >= 0.0
