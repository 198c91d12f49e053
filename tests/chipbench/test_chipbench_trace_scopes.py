"""Device time by part (`harness/trace_scopes.py`) and the eleven readers
built on it, on a hand-made capture whose answers are worked out below:
written out as a real `.xplane.pb` (the wire format, by hand), read back,
billed, and read through every reader as a traced run would."""

import json
import os
import types

import pytest

from chipbench.harness import manifest, trace_scopes

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
US = 1000.0  # the hand-made lists are written in microseconds

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
OLD = 35  # per-layer metrics before PR 38
SELECT = "jit(prefill)/attn.select/sparse_topk_select/while"


def _events(rows):
    return [[name, start * US, (end - start) * US, hlo]
            for hlo, name, start, end in rows]


# Two whole chunks, one whole decode step; a decode call before the first
# recorded operation and one whose operations stop at 4800 (the trace's
# edge) are cut; `jit_admit` is no program of the cell. The first chunk
# holds a loop in a loop: the outer one's self time is 400 - 90 - 250, the
# inner one's 250 - 90, and the gather in the inner body carries a part of
# its own, which wins over the `attn.select` around it.
SERVE = {
    "modules": [[n, s * US, (e - s) * US] for n, s, e in [
        ("jit_decode(2)", 500, 900), ("jit_prefill(1)", 1000, 2000),
        ("jit_decode(2)", 2000, 2600), ("jit_admit(3)", 2700, 2800),
        ("jit_prefill(1)", 3000, 4000), ("jit_decode(2)", 4500, 5200)]],
    "ops": _events([
        ("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput",
         "jit(prefill)/attn.project/dot_general", 1000, 1100),
        ("%while.1 = (s32[]) while((s32[]) %t), body=%b", SELECT, 1100, 1500),
        ("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop",
         SELECT + "/body/mul", 1110, 1200),
        ("%while.2 = (s32[]) while((s32[]) %u), body=%c",
         SELECT + "/body/while", 1200, 1450),
        ("%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %g), kind=kLoop",
         SELECT + "/body/while/body/cache.view/gather", 1210, 1300),
        ('%ragged-dot-none.3 = f32[8]{0} custom-call(bf16[8]{0} %r), '
         'custom_call_target="tpu_custom_call"', "ragged-dot-none",
         1500, 1800),
        ("%copy.1 = bf16[8]{0} copy(bf16[8]{0} %w)", None, 1800, 1850),
        # a FUNCTION called head is not the part
        ("%fusion.4 = f32[8]{0} fusion(f32[8]{0} %h), kind=kLoop",
         "jit(prefill)/jit(head)/add", 1850, 1900),
        ("%fusion.5 = f32[8]{0} fusion(bf16[8]{0} %x), kind=kOutput",
         "jit(prefill)/head/dot_general", 1900, 1990),
        ('%paged = bf16[8]{0} custom-call(bf16[8]{0} %q), '
         'custom_call_target="tpu_custom_call"',
         "jit(decode)/jit(main)/while/body/attn.attend/paged_decode_attention",
         2000, 2200),
        ("%fusion.7 = bf16[8]{0} fusion(bf16[8]{0} %k), kind=kLoop",
         "jit(decode)/cache.write/cache.write/scatter", 2200, 2250),
        ("%fusion.8 = bf16[8]{0} fusion(bf16[8]{0} %m), kind=kOutput",
         "jit(decode)/mlp/dot_general", 2250, 2400),
        ("%fusion.9 = f32[8]{0} fusion(bf16[8]{0} %x), kind=kOutput",
         "jit(decode)/head/dot_general", 2400, 2500),
        ("%fusion.10 = s32[8]{0} fusion(f32[8]{0} %l), kind=kLoop",
         "jit(decode)/sample/vmap(sample)/argmax", 2500, 2520),
        ("%fusion.11 = s32[4]{0} fusion(s32[4]{0} %z), kind=kLoop", None,
         2700, 2750),
        ("%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kOutput",
         "jit(prefill)/attn.project/dot_general", 3000, 3120),
        ("%fusion.5 = f32[8]{0} fusion(bf16[8]{0} %x), kind=kOutput",
         "jit(prefill)/head/dot_general", 3120, 3200),
        ("%fusion.6 = s32[]{:T(128)} fusion(f32[8]{0} %l), kind=kLoop",
         "jit(prefill)/sample/argmax", 3200, 3210),
        ('%paged = bf16[8]{0} custom-call(bf16[8]{0} %q), '
         'custom_call_target="tpu_custom_call"',
         "jit(decode)/jit(main)/while/body/attn.attend/paged_decode_attention",
         4500, 4800)]),
}
# mean microseconds a whole call, worked by hand from the list above
PREFILL = {"attn.project": (100 + 120) / 2,
           "attn.select": (60 + 90 + 160) / 2, "cache.view": 90 / 2,
           "moe.experts": 300 / 2, "head": (90 + 80) / 2, "sample": 10 / 2,
           trace_scopes.UNSCOPED: (50 + 50) / 2}
DECODE = {"attn.attend": 200.0, "cache.write": 50.0, "mlp": 150.0,
          "head": 100.0, "sample": 20.0}
# the backward of a checkpointed scan of layers, the loss's own backward,
# a norm in the head's forward: each under its forward's part
TRAIN = {
    "modules": [["jit_step_fn(7)", 10 * US, 701 * US]],
    "ops": _events([
        ("%fusion.20 = f32[8]{0} fusion(bf16[8]{0} %h), kind=kOutput",
         "jit(step_fn)/jit(main)/jvp(loss)/while/body/closed_call/"
         "bsh,vh->bsv/dot_general", 10, 310),
        ("%fusion.21 = bf16[8]{0} fusion(f32[8]{0} %d), kind=kLoop",
         "jit(step_fn)/jit(main)/transpose(jvp(loss))/mul", 310, 360),
        ("%fusion.22 = bf16[8]{0} fusion(bf16[8]{0} %y), kind=kOutput",
         "jit(step_fn)/jit(main)/transpose(jvp(while))/body/checkpoint/"
         "rematted_computation/mlp/...d,df->...f/dot_general", 360, 560),
        ("%fusion.23 = f32[8]{0} fusion(f32[8]{0} %g), kind=kLoop",
         "jit(step_fn)/jit(main)/optimizer/add", 560, 660),
        ("%fusion.24 = f32[8]{0} fusion(f32[8]{0} %n), kind=kLoop",
         "jit(step_fn)/jit(main)/jvp(head)/rsqrt", 660, 680),
        ("%copy.9 = f32[8]{0} copy(f32[8]{0} %v)", None, 680, 710)]),
}
# the same calls from a program without a scope: the parent of PR 38, or
# what a reader keyed by a kernel's name made of PR 36
BARE = {"modules": SERVE["modules"],
        "ops": [[None, s, d, hlo] for _, s, d, hlo in SERVE["ops"]]}
SERVE_BUSY = 990 + 210 + 520
EXPECTED = {  # metric -> (capture, value) on the hand-made lists
    "step.prefill_attention_device_ms": ("serve", (110 + 155) / 1e3),
    "step.prefill_cache_view_device_ms": ("serve", 45 / 1e3),
    "step.prefill_ffn_device_ms": ("serve", 150 / 1e3),
    "step.prefill_head_device_ms": ("serve", (85 + 5) / 1e3),
    "step.decode_attention_device_ms": ("serve", (200 + 50) / 1e3),
    "step.decode_ffn_device_ms": ("serve", 150 / 1e3),
    "step.decode_head_device_ms": ("serve", (100 + 20) / 1e3),
    "step.train_head_loss_device_ms": ("train", (300 + 50 + 20) / 1e3),
    "step.train_optimizer_device_ms": ("train", 100 / 1e3),
    "device.unscoped_busy_share.serve": ("serve", 100 * 100 / SERVE_BUSY),
    "device.unscoped_busy_share.train": ("train", 100 * 30 / 700),
}
NEW = [m for m in MANIFEST["per_layer"] if m["name"] in EXPECTED]


# -- a capture written out as the profiler writes it ---------------------------


def _varint(n: int) -> bytes:
    assert n >= 0
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _int(field: int, value: int) -> bytes:
    return _varint(field << 3) + _varint(value)


def _bytes(field: int, payload) -> bytes:
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _entry(key: int, message: bytes) -> bytes:
    return _int(1, key) + _bytes(2, message)


def _plane(name: str, device: dict, by_reference: bool) -> bytes:
    """One XPlane of xplane.proto: the operations' `op_name` in their event
    METADATA's `tf_op` stat, `<op_name>:`, as a string or (`by_reference`)
    as the id of a stat metadata whose name is the string."""
    stat_meta = {1: "device_offset_ps", 26: "tf_op", 23: "hlo_op"}
    event_meta, ids = [], {}

    def meta_id(hlo, op_name):
        if (hlo, op_name) not in ids:
            ids[hlo, op_name] = len(ids) + 1
            stats = _bytes(5, _int(1, 23) + _bytes(5, "not this one"))
            if op_name is not None and by_reference:
                stat_meta[100 + len(ids)] = op_name + ":"
                stats += _bytes(5, _int(1, 26) + _int(7, 100 + len(ids)))
            elif op_name is not None:
                stats += _bytes(5, _int(1, 26) + _bytes(5, op_name + ":"))
            event_meta.append(_entry(ids[hlo, op_name], _int(
                1, ids[hlo, op_name]) + _bytes(2, hlo) + stats))
        return ids[hlo, op_name]

    def line(name, origin_ns, events):
        body = _bytes(2, name) + _int(3, origin_ns)
        for hlo, op_name, start, dur in events:
            body += _bytes(4, _int(1, meta_id(hlo, op_name))
                           + _int(2, int((start - origin_ns) * 1000))
                           + _int(3, int(dur * 1000))
                           # an event's own stats: skipped by the reader
                           + _bytes(4, _int(1, 1) + _int(3, 12345)))
        return _bytes(3, body)

    lines = (line("Steps", 0, [])
             + line("XLA Modules", 7, [(n, None, s, d)
                                       for n, s, d in device["modules"]])
             + line("XLA Ops", 3, [(hlo, n, s, d)
                                     for n, s, d, hlo in device["ops"]]))
    return (_int(1, 3) + _bytes(2, name) + lines
            + b"".join(_bytes(4, e) for e in event_meta)
            + b"".join(_bytes(5, _entry(k, _int(1, k) + _bytes(2, v)))
                       for k, v in stat_meta.items()))


def _write_capture(work_dir, device, by_reference=False):
    folder = os.path.join(work_dir, "trace", "plugins", "profile", "run")
    os.makedirs(folder)
    decoy = {"modules": [], "ops": _events([("%other", "mlp", 1, 9)])}
    host = _bytes(2, "/host:CPU") + _bytes(3, _bytes(2, "python"))
    with open(os.path.join(folder, "host.xplane.pb"), "wb") as f:
        f.write(_bytes(1, host)
                + _bytes(1, _plane("/device:TPU:1", decoy, False))
                + _bytes(1, _plane("/device:TPU:0", device, by_reference))
                + _bytes(2, "an error string the reader passes over"))
    return os.path.join(folder, "host.xplane.pb")


def _run(tmp_path, device, programs):
    """What a reader is handed after a traced run of a cell whose scratch
    directory holds the capture."""
    work = str(tmp_path / "work")
    _write_capture(work, device)
    cell = types.SimpleNamespace(shape={"programs": programs},
                                 work_dir=lambda: work)
    return types.SimpleNamespace(
        cell=cell, trace=types.SimpleNamespace(device_names=["/device:TPU:0"]))


CAPTURES = {
    "serve": (SERVE, {"decode": "jit_decode", "prefill": "jit_prefill"}),
    "train": (TRAIN, {"train_step": "step_fn"}),
}


def _reader(metric):
    return manifest.Cell(MANIFEST["workloads"][0]["name"]).layer_reader(metric)


# -- the tests -------------------------------------------------------------------


@pytest.mark.parametrize("op_name,part", [
    ("jit(decode)/jit(main)/while/body/attn.project/dot_general",
     "attn.project"),
    ("jit(step_fn)/jit(main)/transpose(jvp(attn.output))/transpose",
     "attn.output"),
    ("jit(step_fn)/jit(main)/jvp(checkpoint(mlp))/mul", "mlp"),
    ("jit(prefill)/attn.attend/cache.write/dynamic_update_slice",
     "cache.write"),
    ("jit(prefill)/attn.select/sparse_topk_select/while/body/add",
     "attn.select"),
    ("ragged-dot-none", "moe.experts"),
    ("jit(decode)/moe.experts/ragged-dot-rows", "moe.experts"),
    ("jit(prefill)/jit(head)/add", trace_scopes.UNSCOPED),
    ("jit(decode)/attn.projection/dot_general", trace_scopes.UNSCOPED),
    ("params['layers'][3]['attn']['kv_b_proj']['kernel']",
     trace_scopes.UNSCOPED),
    ("", trace_scopes.UNSCOPED),
    (None, trace_scopes.UNSCOPED),
])
def test_the_innermost_known_part_wins(op_name, part):
    assert trace_scopes.part_of(op_name) == part


@pytest.mark.parametrize("by_reference", [False, True],
                         ids=["string", "reference"])
def test_a_capture_is_read_back_from_the_wire_format(tmp_path, by_reference):
    path = _write_capture(str(tmp_path), SERVE, by_reference)
    device = trace_scopes.read_device(path)
    assert device["modules"] == SERVE["modules"]
    assert device["ops"] == SERVE["ops"]


def test_a_capture_without_a_device_plane_reads_as_none(tmp_path):
    path = str(tmp_path / "cpu.xplane.pb")
    with open(path, "wb") as f:
        f.write(_bytes(1, _bytes(2, "/host:CPU")))
    assert trace_scopes.read_device(path) is None


def test_parts_and_unscoped_add_up_to_a_programs_busy_time():
    bills = trace_scopes.bill(SERVE, CAPTURES["serve"][1])
    assert bills["prefill"]["calls"] == 2 and bills["decode"]["calls"] == 1
    for key, want in (("prefill", PREFILL), ("decode", DECODE)):
        got = bills[key]["parts"]
        assert set(got) == set(trace_scopes.PARTS) | {trace_scopes.UNSCOPED}
        for part, t in got.items():
            assert t == pytest.approx(want.get(part, 0.0) * US), (key, part)
        # nothing is counted twice under a loop, nothing is lost
        assert sum(got.values()) == pytest.approx(bills[key]["busy_ns"])
    assert bills["prefill"]["busy_ns"] == pytest.approx((990 + 210) / 2 * US)
    assert bills["decode"]["busy_ns"] == pytest.approx(520 * US)
    # the gauge names what it holds, largest first
    assert [(hlo.split()[0], name) for hlo, name, _ in
            bills["prefill"]["unscoped"]] == [
        ("%copy.1", None), ("%fusion.4", "jit(prefill)/jit(head)/add")]
    assert "moe.experts" in trace_scopes.report(bills)


def test_a_program_without_scopes_reads_zeros_not_nothing():
    bills = trace_scopes.bill(BARE, CAPTURES["serve"][1])
    assert bills["decode"]["parts"][trace_scopes.UNSCOPED] == 520 * US
    assert bills["decode"]["parts"]["attn.attend"] == 0.0
    # XLA's own grouped product is known by the name the compiler gives it
    assert trace_scopes.bill(SERVE, CAPTURES["serve"][1])["prefill"]["parts"][
        "moe.experts"] == 150 * US


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_a_reader_reads_its_parts_of_its_program(tmp_path, metric):
    capture, want = EXPECTED[metric]
    assert _reader(metric).read(_run(tmp_path, *CAPTURES[capture])) == \
        pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_a_reader_gives_a_number_for_an_absent_part_and_none_without_a_trace(
        tmp_path, metric):
    capture, _ = EXPECTED[metric]
    device, programs = CAPTURES[capture]
    bare = {"modules": device["modules"],
            "ops": [[None, s, d, hlo] for _, s, d, hlo in device["ops"]]}
    value = _reader(metric).read(_run(tmp_path, bare, programs))
    assert value == (100.0 if "unscoped" in metric else 0.0)
    # untraced; traced on the CPU (no device plane); no capture; a cell
    # without the program
    read = _reader(metric).read
    run = _run(tmp_path / "again", device, programs)
    assert read(types.SimpleNamespace(cell=run.cell, trace=None)) is None
    assert read(types.SimpleNamespace(cell=run.cell, trace=types.SimpleNamespace(
        device_names=[]))) is None
    empty = types.SimpleNamespace(shape=run.cell.shape,
                                  work_dir=lambda: str(tmp_path / "none"))
    assert read(types.SimpleNamespace(cell=empty, trace=run.trace)) is None
    other = types.SimpleNamespace(shape={"programs": {"verify": "jit_verify"}},
                                  work_dir=run.cell.work_dir)
    assert read(types.SimpleNamespace(cell=other, trace=run.trace)) is None


@pytest.mark.parametrize("entry", NEW, ids=[m["name"] for m in NEW])
def test_a_new_metric_names_its_cells_and_has_a_reader(entry):
    assert len(NEW) == len(EXPECTED) == 11
    assert entry in MANIFEST["per_layer"][OLD:], "appended, not inserted"
    assert entry["source"] == "device_trace" and entry["better"] == "lower"
    cells = {w["name"] for w in MANIFEST["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= cells
    moved = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    train = entry["name"].endswith(".train") or ".train_" in entry["name"]
    assert all(w.startswith("train-") == train for w in entry["workloads"])
    assert os.path.isfile(os.path.join(ROOT, "chipbench", "layer_metrics",
                                       entry["name"] + ".py"))
    for cell in entry["workloads"]:
        programs = manifest.Cell(cell).shape["programs"]
        assert set(programs) == ({"train_step"} if train
                                 else {"decode", "prefill"})
