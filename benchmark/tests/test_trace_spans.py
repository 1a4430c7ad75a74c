"""``trace_spans`` on a few rounds kept beside this file.  Instruction texts,
``tf_op`` strings, program ids, plane and line names in
``recorded_spans.json`` are as the v5e's profiler wrote them for the engine's
two programs with their ``kft.*`` scopes (PR 24; texts cut to 160
characters); the times are set by hand, in nanoseconds, so that every answer
below can be worked out on paper:

    window (host annotation)      1000 .................................. 21000
    modules   decode_rounds 2000-12000, prefill_chunk 13000-15000,
              decode_rounds 16000-20000
    ops       while.37 2000-12000 { qkv_proj 2000-3000,
                  while.36 3000-11000 { kv_view 3000-4000,
                      convert.129 4000-5500 (the loop's tf_op: no scope),
                      attention 5500-9000, kv_write 9000-9500,
                      mlp 9500-11000 },
                  sample 11000-11800 }
              mlp 13000-14500, copy.37 14500-15000 (no tf_op at all)
              while.37 16000-20000 { attention 16000-19000 }
    idle      1000-2000, 12000-13000, 15000-16000, 20000-21000
    phases    (the first annotation the trace caught begins at 12100)
              round_wait 12100-12200, drain 12200-12700, account 12700-13500,
              prefill_dispatch 13500-14800, round_prepare 14800-16100,
              round_dispatch 16100-16300 (facts in its name),
              round_wait 16300-19900,
              drain 19900-20900 { round_wait 20200-20600 }, then nothing

The test writes the recording as a real ``.xplane.pb`` (JAX serialises the
text form), so that ``load`` reads it the way it reads a traced run's file.
"""

import json
import pathlib

import pytest

from benchmark.lib import trace_reduce as tr
from benchmark.lib import trace_spans as ts
from benchmark.lib import window

HERE = pathlib.Path(__file__).parent
REC = json.loads((HERE / "recorded_spans.json").read_text())
T0, T1 = REC["window"]
ROUNDS, CHUNK = "jit_decode_rounds", "jit_prefill_chunk_into_slot"


def _text(s):
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def _xspace_text(rec, marker=(T0, T1)):
    """The recording in the text form of an ``XSpace``."""
    stat_ids = {"tf_op": 1, "program_id": 2, "round": 3, "width": 4,
                "live": 5, "admitted": 6, "chunks": 7}
    stat_meta = "".join(
        f"stat_metadata {{ key: {i} value {{ id: {i} name: {_text(n)} }} }}\n"
        for n, i in stat_ids.items())
    dev = [f"planes {{ id: 1 name: {_text(rec['device_plane'])}\n", stat_meta]
    for m in rec["event_metadata"]:
        stats = f"stats {{ metadata_id: 2 uint64_value: {m['program_id']} }}"
        if m["tf_op"]:
            stats += (f" stats {{ metadata_id: 1 str_value: "
                      f"{_text(m['tf_op'])} }}")
        dev.append(f"event_metadata {{ key: {m['id']} value {{ id: {m['id']} "
                   f"name: {_text(m['name'])} {stats} }} }}\n")
    first_module = 100
    module_ids = {}
    for name, _, _ in rec["modules"]:
        module_ids.setdefault(name, first_module + len(module_ids))
    for name, i in module_ids.items():
        dev.append(f"event_metadata {{ key: {i} value {{ id: {i} "
                   f"name: {_text(name)} }} }}\n")
    dev.append('lines { id: 1 name: "XLA Modules" timestamp_ns: 0\n')
    for name, start, dur in rec["modules"]:
        dev.append(f"events {{ metadata_id: {module_ids[name]} "
                   f"offset_ps: {start * 1000} duration_ps: {dur * 1000} }}\n")
    dev.append('}\nlines { id: 2 name: "XLA Ops" timestamp_ns: 0\n')
    for e in rec["ops"]:
        dev.append(f"events {{ metadata_id: {e['metadata']} offset_ps: "
                   f"{e['start_ns'] * 1000} duration_ps: "
                   f"{e['duration_ns'] * 1000} }}\n")
    dev.append("}\n}\n")
    host = ['planes { id: 2 name: "/host:CPU"\n', stat_meta,
            'event_metadata { key: 1 value { id: 1 name: '
            f'{_text(ts.MARKER)} }} }}\n']
    names = sorted({p["name"] for p in rec["phases"]})
    for i, name in enumerate(names, 2):
        host.append(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                    f"{_text(name)} }} }}\n")
    host.append('lines { id: 1 name: "python" timestamp_ns: 0\n'
                f"events {{ metadata_id: 1 offset_ps: {marker[0] * 1000} "
                f"duration_ps: {(marker[1] - marker[0]) * 1000} }}\n}}\n")
    host.append(f"lines {{ id: 2 name: {_text(rec['loop_thread'])} "
                "timestamp_ns: 0\n")
    for p in rec["phases"]:
        stats = " ".join(f"stats {{ metadata_id: {stat_ids[k]} "
                         f"int64_value: {v} }}"
                         for k, v in p["facts"].items())
        host.append(f"events {{ metadata_id: {names.index(p['name']) + 2} "
                    f"offset_ps: {p['start_ns'] * 1000} duration_ps: "
                    f"{p['duration_ns'] * 1000} {stats} }}\n")
    host.append("}\n}\n")
    return "".join(dev + host)


def _write(tmp_path, marker=(T0, T1), cell="cell", rec=REC):
    from jax.profiler import ProfileData

    directory = tmp_path / cell / "plugins" / "profile" / "2026_09_27"
    directory.mkdir(parents=True)
    path = directory / "host.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        _xspace_text(rec, marker)))
    return str(path)


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    return ts.load(_write(tmp_path_factory.mktemp("trace")), T0, T1)


def test_facts_are_parsed_off_the_name_and_taken_from_the_statistics():
    assert ts.split_facts("kft.engine.round_dispatch#round=8,width=4,live=16#") \
        == ("kft.engine.round_dispatch", {"round": 8, "width": 4, "live": 16})
    assert ts.split_facts("kft.engine.drain", [("round", "7"), ("_c", 3)]) \
        == ("kft.engine.drain", {"round": 7})
    assert ts.split_facts("kft.engine.admit#note=queue full,x=1.5#") \
        == ("kft.engine.admit", {"note": "queue full", "x": 1.5})
    assert ts.split_facts("bench.trace_window") == ("bench.trace_window", {})


def test_scope_is_the_innermost_kft_component():
    assert ts.scope_of(
        "jit(decode_rounds)/while/body/while/body/closed_call/kft.attention/"
        "bhqk,bkhd->bqhd/dot_general:") == "kft.attention"
    assert ts.scope_of("jit(f)/kft.mlp/kft.kv_write/scatter:") \
        == "kft.kv_write"
    assert ts.scope_of("jit(decode_rounds)/while/body/while:") is None
    assert ts.scope_of(None) is None


def test_the_metadata_table_is_read_from_the_files_bytes(tmp_path):
    table = ts.op_names(_write(tmp_path))
    assert list(table) == ["/device:TPU:0"]  # the host plane is left out
    by_scope = {}
    for (program, text), op in table["/device:TPU:0"].items():
        by_scope.setdefault(ts.scope_of(op), []).append((program, text))
    assert sorted(k for k in by_scope if k) == [
        "kft.attention", "kft.kv_view", "kft.kv_write", "kft.mlp",
        "kft.qkv_proj", "kft.sample"]
    # Two programs each have a kft.mlp fusion; entries with no tf_op at all
    # (the loops, copy.37) are not in the table.
    assert len(by_scope["kft.mlp"]) == 2
    assert {p for p, _ in by_scope["kft.mlp"]} == {
        15624854951524155898, 6749402522105188766}
    assert [t.split(" ")[0] for _, t in by_scope[None]] == ["%convert.129"]


def test_phases_come_with_their_facts_in_time_order(spans):
    phases = spans["phases"]
    assert [p[0] for p in phases] == [
        "round_wait", "drain", "account", "prefill_dispatch",
        "round_prepare", "round_dispatch", "round_wait", "drain",
        "round_wait"]
    assert phases[0][1:] == (12100, 100, {"round": 7})
    assert phases[3][3] == {"round": 8, "admitted": 1, "chunks": 1}
    assert phases[5][3] == {"round": 8, "width": 4, "live": 16}


def test_operations_get_their_module_and_scope(spans):
    ops = spans["ops"]["/device:TPU:0"]
    assert len(ops) == 13
    found = {(name.split(" ")[0], module, scope)
             for name, _, _, module, scope in ops}
    assert ("%while.37", ROUNDS, None) in found
    assert ("%convert.129", ROUNDS, None) in found
    assert ("%multiply_reduce_fusion.5", ROUNDS, "kft.attention") in found
    assert ("%fusion.159", CHUNK, "kft.mlp") in found
    assert ("%copy.37", CHUNK, None) in found


def test_scope_times_through_a_while_body_nested_in_its_loop(spans):
    times = ts.scope_times(spans["ops"]["/device:TPU:0"])
    ns = {key: round(seconds * 1e9) for key, seconds in times.items()}
    assert ns == {
        (ROUNDS, "kft.attention"): 3500 + 3000,
        (ROUNDS, "kft.kv_view"): 1000,
        (ROUNDS, "kft.kv_write"): 500,
        (ROUNDS, "kft.mlp"): 1500,
        (ROUNDS, "kft.qkv_proj"): 1000,
        (ROUNDS, "kft.sample"): 800,
        # convert.129 1500, while.36 nothing of its own, while.37 200 in
        # the first call and 1000 in the second.
        (ROUNDS, None): 1500 + 0 + 200 + 1000,
        (CHUNK, "kft.mlp"): 1500,
        (CHUNK, None): 500,
    }
    assert sum(ns.values()) == tr.busy_ns(
        [o[:3] for o in spans["ops"]["/device:TPU:0"]])


def _gaps(spans):
    ops = [o[:3] for o in spans["ops"]["/device:TPU:0"]]
    return tr.idle_gaps(ops, T0, T1)


def test_a_gap_inside_one_phase_one_over_three_and_one_before_any(spans):
    by_start = {g[0]: g for g in _gaps(spans)}
    assert sorted(by_start) == [1000, 12000, 15000, 20000]
    phases = spans["phases"]
    one = ts.attribute_gaps([by_start[15000]], phases)
    assert one == ({"round_prepare": pytest.approx(1000e-9)}, 0.0)
    three, rest = ts.attribute_gaps([by_start[12000]], phases)
    assert {k: round(v * 1e9) for k, v in three.items()} == {
        "round_wait": 100, "drain": 500, "account": 300}
    assert rest == pytest.approx(100e-9)  # before the first annotation
    before = ts.attribute_gaps([by_start[1000]], phases)
    assert before == ({}, pytest.approx(1000e-9))
    # A phase inside another owns its stretch: the blocking read in a drain.
    nested, rest = ts.attribute_gaps([by_start[20000]], phases)
    assert {k: round(v * 1e9) for k, v in nested.items()} == {
        "drain": 500, "round_wait": 400}
    assert rest == pytest.approx(100e-9)  # after the last annotation
    owned, unattributed = ts.attribute_gaps(_gaps(spans), phases)
    assert round(sum(owned.values()) * 1e9) == 2800
    assert unattributed == pytest.approx(1200e-9)


def test_innermost_cuts_nested_phases_into_a_flat_timeline():
    flat = ts.innermost([("drain", 100, 100, {}), ("round_wait", 120, 30, {}),
                         ("account", 200, 50, {})])
    assert flat == [(100, 120, "drain"), (120, 150, "round_wait"),
                    (150, 200, "drain"), (200, 250, "account")]


def test_a_foreign_trace_is_refused(tmp_path):
    path = _write(tmp_path, marker=(T0 + 5, T1))
    with pytest.raises(ValueError, match="not this run's trace"):
        ts.load(path, T0, T1)
    assert ts.load(path, T0 + 5, T1)["phases"]


def test_the_newest_file_under_the_trace_root_is_taken(tmp_path):
    import os

    old = _write(tmp_path, cell="a")
    new = _write(tmp_path, cell="b")
    os.utime(old, (1, 1))
    assert ts.newest_xplane(tmp_path) == new
    with pytest.raises(FileNotFoundError):
        ts.newest_xplane(tmp_path / "nothing")


def _run(tmp_path, monkeypatch, rec=REC):
    """A traced run as ``run.py`` hands it to the readers, of a program
    that keeps none of the new counters."""
    monkeypatch.setattr(ts, "TRACE_ROOT", tmp_path)
    monkeypatch.setattr(ts, "_LOADED", {})
    path = _write(tmp_path, rec=rec)
    trace = tr.device_summary(tr.load(path), ts.MARKER)
    return {"trace": trace, "window": {"seconds": 50.0},
            "counters": {"before": {"steps": 0}, "at_close": {"steps": 10}}}


def _reader(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "reader_" + name.replace(".", "_"),
        HERE.parent / "layer_metrics" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_trace_readers_on_the_recording(tmp_path, monkeypatch, capsys):
    run = _run(tmp_path, monkeypatch)
    assert _reader("programs.decode_attention_share")(run) == \
        pytest.approx(100 * 7500 / 14000)
    assert _reader("programs.decode_kv_write_share")(run) == \
        pytest.approx(100 * 500 / 14000)
    assert _reader("programs.scope_unowned_share")(run) == \
        pytest.approx(100 * 3200 / 16000)
    # The 1100 ns of idle time before the first annotation the trace caught
    # (12100) are left out; of the other 2900 ns, 2800 lie inside a phase.
    assert _reader("device.idle_attributed_share")(run) == \
        pytest.approx(100 * 2800 / 2900)
    logged = capsys.readouterr().out
    assert "unowned 0.0000 s convert.129 f32[2560,16,8,128]" in logged
    assert "idle 0.0000 s in round_prepare" in logged
    assert "in no phase" in logged
    assert "0.0000 s before the loop's first annotation" in logged


def test_readers_find_nothing_in_a_run_of_the_parent_commit(
        tmp_path, monkeypatch):
    """No scope, no phase, no counter: every reader returns None."""
    bare = json.loads(json.dumps(REC))
    bare["phases"] = []
    for m in bare["event_metadata"]:
        m["tf_op"] = (m["tf_op"] or "").replace("kft.", "") or None
    run = _run(tmp_path, monkeypatch, rec=bare)
    for name in ("programs.decode_attention_share",
                 "programs.decode_kv_write_share",
                 "programs.scope_unowned_share",
                 "device.idle_attributed_share", "engine.queue_wait_ms",
                 "engine.prefill_span_ms", "kv.prefill_span_hit_ms",
                 "engine.device_wait_share", "engine.compile_s",
                 "programs.compiled_peak_gb"):
        assert _reader(name)(run) is None, name
    run["trace"] = None  # and an untraced run
    assert _reader("programs.scope_unowned_share")(run) is None
    assert _reader("device.idle_attributed_share")(run) is None


def test_the_counter_readers_read_the_window(tmp_path, monkeypatch):
    run = {"window": {"seconds": 50.0}, "counters": {
        "before": {"queue_wait_s_sum": 10.0, "admitted": 13,
                   "prefill_span_s_sum": 20.0, "first_tokens": 12,
                   "prefill_span_hit_s_sum": 1.0, "first_tokens_hit": 2,
                   "loop_round_wait_s": 30.0, "compile_s": 4.5,
                   "compiled_peak_bytes": 13_620_000_000},
        "at_close": {"queue_wait_s_sum": 70.0, "admitted": 33,
                     "prefill_span_s_sum": 68.0, "first_tokens": 36,
                     "prefill_span_hit_s_sum": 10.0, "first_tokens_hit": 8,
                     "loop_round_wait_s": 79.0, "compile_s": 4.5,
                     "compiled_peak_bytes": 13_620_000_000}}}
    assert _reader("engine.queue_wait_ms")(run) == pytest.approx(3000.0)
    assert _reader("engine.prefill_span_ms")(run) == pytest.approx(2000.0)
    assert _reader("kv.prefill_span_hit_ms")(run) == pytest.approx(1500.0)
    assert _reader("engine.device_wait_share")(run) == pytest.approx(98.0)
    assert _reader("engine.compile_s")(run) == 4.5
    assert _reader("programs.compiled_peak_gb")(run) == pytest.approx(13.62)
    assert window.grown(run, "admitted") == 20
    assert window.grown(run, "nothing") is None
    # Nobody admitted in the window: no mean, not a division by zero.
    run["counters"]["at_close"]["admitted"] = 13
    assert _reader("engine.queue_wait_ms")(run) is None
