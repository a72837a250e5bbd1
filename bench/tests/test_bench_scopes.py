"""The program's scopes and spans in a trace (``lib/scopes.py``) and the
readers built on them: scope paths from the ``.xplane.pb``, the summary
with and without them, the clock from the marks, the idle gaps named by
the program's spans, each new reader on a made-up trace and over a program
that has none of these, and an excerpt of a traced serving run on a v5e."""
import os
import types

import pytest

from lib import common, scopes, trace
from repro.runtime.spans import CLOCK, Record

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000                  # nanoseconds


@pytest.mark.parametrize("op_name,path", [
    ("jit(dstep)/layers/while/body/attn/kv_cache/select_n", "layers/attn/kv_cache"),
    ("jit(train_step)/transpose(jvp(layers))/while/body/closed_call/"
     "checkpoint/rematted_computation/mlp/jit(silu)/mul", "layers/remat/mlp"),
    ("jit(train_step)/jvp(layers)/while/body/closed_call/attn/jit(attention)/"
     "flash_fwd/pallas_call:", "layers/attn/flash_fwd"),
    ("jit(train_step)/transpose(jvp(unembed))/...d,vd->...v/dot_general",
     "unembed/...d,vd->...v"),
    ("jit(<lambda>)/dot_general:", ""),
    ("jit(f)/attn/flash_fwd/pallas_call", "attn/flash_fwd"),
    ("", ""),
])
def test_scope_path(op_name, path):
    assert scopes.scope_path(op_name) == path


def _write_xspace(path):
    """One TPU with a decode program (a scoped fusion, a kernel whose
    op_name is a referenced string, an op with no op_name) and a host with
    a program span and another event."""
    space = scopes._schema()()
    dev = space.planes.add(name="/device:TPU:0")
    for k, name in ((1, "tf_op"), (2, "jit(dstep)/layers/attn/decode_attn/"
                                       "pallas_call")):
        e = dev.stat_metadata.add(key=k)
        e.value.id, e.value.name = k, name
    meta = {10: "jit_dstep(123)",
            11: "%fusion.3 = bf16[2] fusion(bf16[2] %p), kind=kLoop",
            12: '%decode_attn.1 = bf16[2] custom-call(bf16[2] %q), '
                'custom_call_target="tpu_custom_call"',
            13: "%copy.1 = bf16[2] copy(bf16[2] %p)"}
    for k, name in meta.items():
        e = dev.event_metadata.add(key=k)
        e.value.id, e.value.name = k, name
        if k == 11:
            e.value.stats.add(metadata_id=1, str_value="jit(dstep)/layers/"
                              "while/body/attn/kv_cache/select_n:")
        if k == 12:
            e.value.stats.add(metadata_id=1, ref_value=2)
    mods = dev.lines.add(name=trace.MODULES_LINE, timestamp_ns=1000)
    mods.events.add(metadata_id=10, offset_ps=0, duration_ps=10 * MS * 1000)
    ops = dev.lines.add(name=trace.OPS_LINE, timestamp_ns=1000)
    ops.events.add(metadata_id=11, offset_ps=1_500, duration_ps=2_000_750)
    ops.events.add(metadata_id=12, offset_ps=3 * MS * 1000,
                   duration_ps=4 * MS * 1000)
    ops.events.add(metadata_id=13, offset_ps=8 * MS * 1000,
                   duration_ps=1 * MS * 1000)
    host = space.planes.add(name="/host:CPU")
    for k, name in ((1, "serve.step"), (2, "PjRtExecute")):
        e = host.event_metadata.add(key=k)
        e.value.id, e.value.name = k, name
    line = host.lines.add(name="python", timestamp_ns=500)
    line.events.add(metadata_id=1, offset_ps=0, duration_ps=9 * MS * 1000)
    line.events.add(metadata_id=2, offset_ps=1000, duration_ps=1000)
    os.makedirs(path)
    with open(os.path.join(path, "h.xplane.pb"), "wb") as f:
        f.write(space.SerializeToString())


def test_extract_reads_scope_paths(tmp_path):
    _write_xspace(tmp_path / "plugins" / "profile" / "1")
    tr = scopes.extract(str(tmp_path), {"serve.step"})
    assert tr["devices"] == {"/device:TPU:0": [
        ["fusion.3", 1001, 2000, "jit_dstep", "op", "layers/attn/kv_cache"],
        ["decode_attn.1", 1000 + 3 * MS, 4 * MS, "jit_dstep", "kernel",
         "layers/attn/decode_attn"],
        ["copy.1", 1000 + 8 * MS, MS, "jit_dstep", "op", ""]]}
    assert tr["host"] == [["serve.step", 500, 9 * MS]]


def small():
    """Device 0: a decode program's ops with scopes, 0-10 ms; device 1 the
    same at half length; and a loop around the first op (busy only)."""
    d0 = [["while.1", 0, 10 * MS, "jit_dstep", "op", "layers"],
          ["fusion.1", 0, 4 * MS, "jit_dstep", "op", "layers/attn/kv_cache"],
          ["fusion.2", 4 * MS, 2 * MS, "jit_dstep", "op", "layers"],
          ["dot.1", 6 * MS, 3 * MS, "jit_dstep", "op", "layers/mlp"],
          ["attn.1", 9 * MS, 1 * MS, "jit_dstep", "kernel", ""]]
    d1 = [[n, s // 2, d // 2, p, k, sc] for n, s, d, p, k, sc in d0]
    return {"devices": {"/device:TPU:0": d0, "/device:TPU:1": d1},
            "host": [["step", 0, 20 * MS]]}


def test_summarize_adds_scopes_and_keeps_the_rest():
    tr = small()
    five = {"devices": {p: [e[:5] for e in ev]
                        for p, ev in tr["devices"].items()},
            "host": tr["host"]}
    with_scopes = scopes.summarize(tr, 0.020)
    plain = scopes.summarize(five, 0.020)
    assert plain["scopes_s"] == {}
    assert {k: v for k, v in with_scopes.items() if k != "scopes_s"} == \
        {k: v for k, v in plain.items() if k != "scopes_s"} == \
        trace.summarize(five, 0.020)
    # (4 + 2) / 2 ms on each path, averaged over two devices; the loop
    # counts in busy time only
    s = with_scopes["scopes_s"]
    assert s == {"jit_dstep/layers/attn/kv_cache": pytest.approx(0.003),
                 "jit_dstep/layers": pytest.approx(0.0015),
                 "jit_dstep/layers/mlp": pytest.approx(0.00225),
                 "jit_dstep/": pytest.approx(0.00075)}
    part, whole = scopes.scope_seconds(with_scopes, "dstep",
                                       lambda segs: "attn" in segs)
    assert part == pytest.approx(0.003) and whole == pytest.approx(0.0075)


OFF = -5_000_000_000            # trace ns = perf_counter s * 1e9 + OFF


def _rec(name, t0, t1, parent=None, rid=None, **info):
    return Record(name, t0, t1, info, parent, rid)


def serve_records():
    """Two steps (5.000-5.050 s, the first admitting request 3, and
    5.060-5.100 s), a clock mark at the start of each."""
    return [
        _rec(CLOCK, 5.000, 5.000),
        _rec("serve.step", 5.000, 5.050),
        _rec("serve.admit", 5.001, 5.021, 1, 3, slot=0),
        _rec("serve.prefill", 5.001, 5.011, 2, 3, len=64),
        _rec("serve.slot_update", 5.011, 5.013, 2, 3, slot=0),
        _rec("serve.first_token", 5.013, 5.021, 2, 3),
        _rec("serve.upload", 5.021, 5.024, 1),
        _rec("serve.decode", 5.024, 5.026, 1, active=1),
        _rec("serve.sample", 5.026, 5.046, 1),
        _rec("serve.lanes", 5.046, 5.050, 1),
        _rec(CLOCK, 5.060, 5.060),
        _rec("serve.step", 5.060, 5.100),
        _rec("serve.upload", 5.060, 5.064, 11),
        _rec("serve.decode", 5.064, 5.066, 11, active=1),
        _rec("serve.sample", 5.066, 5.096, 11),
        _rec("serve.lanes", 5.096, 5.100, 11)]


def decode_ops(t):
    """One decode step from ``t`` ms, 20 ms: cache write 6, the loop's own
    slicing 4, attention 3, MLP 5, unembedding 2."""
    out, s = [], t
    for n, d, sc in (("select.1", 6, "layers/attn/kv_cache"),
                     ("dus.1", 4, "layers"), ("dot.1", 3, "layers/attn"),
                     ("dot.2", 5, "layers/mlp"), ("dot.3", 2, "unembed")):
        out.append([n, s * MS, d * MS, "jit_dstep", "op", sc])
        s += d
    return out


def serve_trace(with_scopes=True):
    ops = ([["fusion.9", 4 * MS, 15 * MS, "jit_prefill_step", "op",
             "layers/attn"],
            ["dus.7", 19 * MS, 1 * MS, "jit__update_slot", "op", ""],
            ["argmax.1", 20 * MS, MS // 2, "jit_argmax", "op", ""]]
           + decode_ops(25) + decode_ops(65))
    if not with_scopes:
        ops = [e[:5] + [""] for e in ops]
    marks = [[CLOCK, 0 + 2000, 0], [CLOCK, 60 * MS - 3000, 0]]
    decodes = [["serve.decode", 24 * MS + 1000, 2 * MS],
               ["serve.decode", 64 * MS + 500, 2 * MS]]
    return {"devices": {"/device:TPU:0": ops}, "host": marks + decodes}


def reading(monkeypatch, tr, records, window=(5.0, 5.1)):
    """A reading of a traced run whose program kept ``records`` (None: a
    program without ``repro.runtime.spans``) and whose trace is ``tr``."""
    if records is None:
        module = None
    else:
        module = types.SimpleNamespace(CLOCK=CLOCK, PROGRAM=types.
                                       SimpleNamespace(records=records,
                                                       counters={}))
    monkeypatch.setattr(scopes, "_spans_module", lambda: module)
    monkeypatch.setattr(scopes, "extract", lambda d, names=None: tr)
    ctx = Ctx()
    ctx.trace_window = window
    return types.SimpleNamespace(ctx=ctx, out={}, summary=None, peaks={})


class Ctx:
    """A run's context as the readers see it (``bench/run.Context``)."""


def test_clock_from_the_marks():
    clk = scopes.clock(serve_records(), serve_trace()["host"], CLOCK)
    (a, oa), (b, ob) = clk
    assert (a, b) == (5.000, 5.060)
    assert oa == pytest.approx(OFF + 2000) and ob == pytest.approx(OFF - 3000)
    # halfway between the marks the offset is halfway between the two
    assert scopes.to_trace_ns(5.030, clk) == pytest.approx(30 * MS - 500)
    # least of the nearest marks: a late event does not move it
    host = [[CLOCK, 0, 0], [CLOCK, 90_000, 0]]
    recs = [_rec(CLOCK, 5.0, 5.0), _rec(CLOCK, 5.0, 5.0)]
    assert scopes.clock(recs, host, CLOCK)[0][1] == pytest.approx(OFF)
    assert scopes.clock(recs[:1], host, CLOCK) is None


def test_gaps_named_by_the_program_spans(monkeypatch):
    r = reading(monkeypatch, serve_trace(), serve_records())
    pt = scopes.program_trace(r)
    # the offset moves by -5 us over the 60 ms between the marks
    assert pt["window_ns"] == (pytest.approx(2000), pytest.approx(
        100 * MS + 2000 - 5000 * 100 / 60))
    assert scopes.decode_alignment_ns(pt) < 5000
    named = {n: s for n, s in scopes.named_gaps(pt["gaps"], pt["spans"])}
    # busy 4-20.5, 25-45, 65-85 ms: gaps 0-4 (mostly in the prefill's
    # span), 20.5-25 (the upload), 45-65 (between steps), 85-100 (the
    # sample)
    assert set(named) == {"serve.prefill", "serve.upload", "none",
                          "serve.sample"}
    assert named["none"] == pytest.approx(0.020)
    assert named["serve.sample"] == pytest.approx(0.015, abs=1e-5)
    # apportioned: 0-4 ms is the step before its admission, then the
    # prefill; 20.5-25 the first token, the upload, the decode; 45-65 the
    # sample, the lanes, no span (between steps), the next upload and
    # decode; 85-100 the sample and the lanes
    by_span = scopes.idle_by_span(pt["gaps"], pt["spans"])
    assert by_span == {k: pytest.approx(v * 1e-3, abs=2e-5) for k, v in {
        "serve.step": 1, "serve.prefill": 3, "serve.first_token": 0.5,
        "serve.upload": 7, "serve.decode": 2, "serve.sample": 12,
        "serve.lanes": 8, "none": 10}.items()}
    assert sum(by_span.values()) == pytest.approx(
        sum(e - s for s, e in pt["gaps"]) * 1e-9)


def test_serving_readers(monkeypatch):
    r = reading(monkeypatch, serve_trace(), serve_records())
    idle = common.metric_reader("engine_idle_share.serve").read(r)
    # idle in steps: 4 + 4.5 + (45-50) 5 + (60-65) 5 + 15 of 100 ms
    assert idle == pytest.approx(33.5, abs=0.02)
    admit = common.metric_reader("admit_share.serve").read(r)
    assert admit == pytest.approx(100 * 20 / 90)
    cache = common.metric_reader("decode_cache_share.serve").read(r)
    assert cache == pytest.approx(100 * (6 + 4) / 20)


def test_training_reader(monkeypatch):
    ops = [["f.1", 0, 10 * MS, "jit_train_step", "op", "layers/attn"],
           ["f.2", 10 * MS, 3 * MS, "jit_train_step", "op", "unembed"],
           ["f.3", 13 * MS, 1 * MS, "jit_train_step", "op", "loss"],
           ["f.4", 14 * MS, 5 * MS, "jit_train_step", "op", "layers/mlp"],
           ["f.5", 19 * MS, 1 * MS, "jit_train_step", "op", "optimizer"],
           ["f.6", 20 * MS, 5 * MS, "jit_feed", "op", "loss"]]
    r = reading(monkeypatch, {"devices": {"/device:TPU:0": ops}, "host": []},
                records=[])
    read = common.metric_reader("unembed_loss_share.train").read
    assert read(r) == pytest.approx(100 * 4 / 20)
    # a program that keeps no spans: the device-trace reader still reads
    r = reading(monkeypatch, {"devices": {"/device:TPU:0": ops}, "host": []},
                records=None)
    assert read(r) == pytest.approx(100 * 4 / 20)


NEW = ("engine_idle_share.serve", "admit_share.serve",
       "decode_cache_share.serve", "unembed_loss_share.train")


@pytest.mark.parametrize("records", [None, []], ids=["no_spans", "no_records"])
def test_readers_report_nothing_without_spans_or_scopes(monkeypatch, records):
    """Over a program without ``repro.runtime.spans`` or named scopes (an
    older checkout), or one whose spans recorded nothing, each new
    reader returns None: the metric is left out, not read as 0."""
    for name in NEW:
        r = reading(monkeypatch, serve_trace(with_scopes=False), records)
        assert common.metric_reader(name).read(r) is None, name


def test_recorded_chip_trace_excerpt():
    """An excerpt of a traced serving run on a v5e (see the file's
    ``about``): the clock marks at both ends of the traced stretch agree,
    each ``serve.decode`` record lands on its event, every idle gap over
    0.5 ms that lies mostly inside a step is named by an engine phase, and
    the decode program's time under the cache's scopes is what the file
    says."""
    rec = common.load_json(DATA, "trace_v5e_spans.json")
    records = [Record(*r) for r in rec["records"]]
    tr = rec["trace"]
    clk = scopes.clock(records, tr["host"], CLOCK)
    (_, o0), (_, o1) = clk
    assert abs(o1 - o0) < 50_000
    sh = scopes.shifted(records, clk)
    recs = sorted(s for n, s, _ in sh if n == "serve.decode")
    evs = sorted(h[1] for h in tr["host"] if h[0] == "serve.decode")
    assert len(recs) == len(evs) >= 1
    assert max(abs(a - b) for a, b in zip(recs, evs)) < 50_000
    lo, hi = rec["window_ns"]
    gaps = [g for g in scopes.idle_gaps(tr, lo, hi) if g[1] - g[0] > 0.5 * MS]
    in_steps = [n for n, _ in scopes.named_gaps(gaps, sh) if n != "none"]
    assert in_steps
    for name in in_steps:
        assert name.startswith("serve.") and name != "serve.step", name
    summary = scopes.summarize(tr, (hi - lo) * 1e-9)
    moves = common.metric_reader("decode_cache_share.serve").moves_cache
    part, whole = scopes.scope_seconds(summary, "dstep", moves)
    assert 0 < part < whole
    assert part / whole == pytest.approx(rec["expected"]["decode_cache_part"],
                                         rel=1e-6)
