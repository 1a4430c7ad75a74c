"""The five readers of PR 39 (``engine.turnaround_ms``,
``engine.round_read_ms``, ``engine.loop_cpu_share``,
``engine.slow_round_share``, ``device.idle_blocked_share``) on made-up
runs: counters before / at close as ``run.py`` hands them over, and the
recording of ``test_trace_spans.py`` with a ``round_read`` put inside
each of its ``round_wait`` annotations:

    round_wait 12100-12200 { round_read 12150-12200 }     idle 12100-13000
    drain 12200-12700, account 12700-13500
    prefill_dispatch 13500-14800 (chunks 1, since_ready_us 2)
    round_prepare 14800-16100                             idle 15000-16000
    round_dispatch 16100-16300
    round_wait 16300-19900 { round_read 19700-19900 }     busy
    drain 19900-20900 { round_wait 20200-20600            idle 20000-21000
                        { round_read 20400-20600 } }

Of the 2,900 ns idle after the first annotation ``round_wait`` owns 50 +
200; the one turnaround whose ends the trace holds runs from 12150 to the
chunk's dispatch, and the device's idle time from 12150 to its next
operation (13000) is 850 ns.  The times are set by hand and no clock could
have written them: the chunk's operation starts 500 ns before its dispatch
does, so the skew reads as at least 500 ns; the gap is cut at the first
annotation, where the gaps are read from, and gives no upper bound.
"""

import json

import pytest
from test_trace_spans import REC, _reader, _run

PHASES = ("admit", "housekeeping", "prefill_dispatch", "round_prepare",
          "round_dispatch", "overlap", "round_read", "drain", "account")
NEW = ("engine.turnaround_ms", "engine.round_read_ms",
       "engine.loop_cpu_share", "engine.slow_round_share",
       "device.idle_blocked_share")


def _counters(scale):
    """``stats()`` of the change at two readings: every counter grows in
    proportion, so that the window's figures can be worked out by hand."""
    out = {"fused_rounds": 100 * scale, "turnarounds": 90 * scale,
           "turnaround_s_sum": 0.45 * scale, "loop_cpu_s": 1.6 * scale,
           "slow_rounds": scale, "slow_round_s_sum": 0.5 * scale,
           "loop_wait_work_s": 7.0 * scale, "loop_round_wait_s": 40 * scale}
    out.update({f"loop_{p}_s": 0.2 * scale for p in PHASES})
    out["loop_round_read_s"] = 0.4 * scale
    return out


def _window():
    return {"window": {"seconds": 50.0}, "trace": None,
            "counters": {"before": _counters(1), "at_close": _counters(3)}}


def test_the_counter_readers_read_the_window():
    run = _window()
    # 0.9 s over 180 turnarounds, 0.8 s of reads over 200 rounds, 3.2 CPU
    # seconds over 2 x (8 x 0.2 + 0.4) unblocked ones, 1 s of 50.
    assert _reader("engine.turnaround_ms")(run) == pytest.approx(5.0)
    assert _reader("engine.round_read_ms")(run) == pytest.approx(4.0)
    assert _reader("engine.loop_cpu_share")(run) == pytest.approx(80.0)
    assert _reader("engine.slow_round_share")(run) == pytest.approx(2.0)
    assert _reader("device.idle_blocked_share")(run) is None  # untraced


def test_a_window_without_a_round_or_a_slow_iteration():
    run = _window()
    at_close = run["counters"]["at_close"]
    for key in ("turnarounds", "fused_rounds", "slow_round_s_sum"):
        at_close[key] = run["counters"]["before"][key]
    assert _reader("engine.turnaround_ms")(run) is None
    assert _reader("engine.round_read_ms")(run) is None
    assert _reader("engine.slow_round_share")(run) == 0.0
    for phase in PHASES:
        at_close[f"loop_{phase}_s"] = \
            run["counters"]["before"][f"loop_{phase}_s"]
    assert _reader("engine.loop_cpu_share")(run) is None


def _with_reads():
    rec = json.loads(json.dumps(REC))
    reads = []
    for p in rec["phases"]:
        if p["name"] == "kft.engine.round_wait":
            length = min(200, p["duration_ns"] // 2)
            reads.append({
                "name": "kft.engine.round_read", "duration_ns": length,
                "start_ns": p["start_ns"] + p["duration_ns"] - length,
                "facts": {"round": p["facts"]["round"]}})
        elif p["name"] == "kft.engine.prefill_dispatch":
            p["name"] += "#since_ready_us=2#"
    rec["phases"] = sorted(rec["phases"] + reads,
                           key=lambda p: (p["start_ns"], -p["duration_ns"]))
    return rec


def test_idle_blocked_share_on_the_recording(tmp_path, monkeypatch, capsys):
    run = _run(tmp_path, monkeypatch, rec=_with_reads())
    run["counters"] = {"before": _counters(1), "at_close": _counters(3)}
    read = _reader("device.idle_blocked_share")
    # The recording's gaps are 1,000 ns long: under the reader's least
    # length of a turnaround's gap none is one (the share is of all).
    assert read(run) == pytest.approx(100 * 250 / 2900)
    assert "next operation 0.0000 s; the device's clock reads between " \
        "-inf and inf ms" in capsys.readouterr().out
    monkeypatch.setitem(read.__globals__, "LEAST_GAP_NS", 500)
    assert read(run) == pytest.approx(100 * 250 / 2900)
    # The older reader gives the inner annotation its stretch too.
    assert _reader("device.idle_attributed_share")(run) == \
        pytest.approx(100 * 2800 / 2900)
    logged = capsys.readouterr().out
    assert "idle by phase, ms a round over 1 rounds: round_prepare 0.001" \
        in logged
    assert "one clock: 1 turnarounds in the trace, the program's " \
        "since_ready_us 0.0000 s" in logged
    # The one gap begins at the first annotation, where the gaps are read
    # from: its start is no operation's end and bounds nothing.
    assert "reads between 0.001 and inf ms behind the host's" in logged
    module = _reader("device.idle_blocked_share").__globals__
    spans = module["trace_spans"].of_run(run)
    assert module["turnarounds"](spans["phases"]) == [
        (12150, 13500, 14800, 2)]
    gaps = [(1000, 1000), (12000, 1000), (15000, 1000), (20000, 1000)]
    gap_around = module["gap_around"]
    assert gap_around(gaps, 12150, 14800) == (12000, 13000)
    assert gap_around(gaps, 11900, 14800) == (12000, 13000)  # busy when told
    assert gap_around(gaps, 13500, 14800) is None  # the next gap: another's
    assert gap_around(gaps, 21500, 30000) is None


def test_the_new_readers_find_nothing_in_a_run_of_the_parent_commit(
        tmp_path, monkeypatch):
    """The parent's program: ten phases, no ``round_read``, none of the
    new counters.  Every new reader returns None and does not raise, on a
    traced run as on an untraced one."""
    run = _run(tmp_path, monkeypatch)
    run["counters"] = {
        "before": {"steps": 0, "fused_rounds": 5, "loop_round_wait_s": 1.0},
        "at_close": {"steps": 9, "fused_rounds": 9,
                     "loop_round_wait_s": 40.0}}
    for name in NEW:
        assert _reader(name)(run) is None, name
    run["trace"] = None
    for name in NEW:
        assert _reader(name)(run) is None, name


def test_the_manifest_lists_the_five_for_every_cell():
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[2]
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in manifest["workloads"]]
    listed = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"]][-5:] == list(NEW)
    for name in NEW:
        assert listed[name]["workloads"] == cells
        assert listed[name]["moves"] == "tpot_ms"
        assert (root / "benchmark" / "layer_metrics" / f"{name}.py").exists()
    assert {listed[n]["layer"] for n in NEW} == {"engine", "device"}
