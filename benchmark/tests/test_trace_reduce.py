"""``trace_reduce`` on a small trace kept beside this file.  Planes, lines and
operation names are as the v5e's profiler wrote them for this benchmark's
chat cell (PR 23); the times are set by hand, in nanoseconds, so that every
answer below can be worked out on paper:

    window (host annotation)      500 ...................... 10500
    modules   decode_rounds 1000-5000, prefill_chunk 6000-7000,
              decode_rounds 7500-10000
    ops       while 1000-5000 { copy-done 1000-2000, fusion.145 2000-2500,
                                copy-done 3000-5000 }
              fusion.9 6000-7000
              while 7500-10000 { copy-done 7500-9500 }
"""

import json
import pathlib

import pytest

from benchmark.lib import trace_reduce as tr

TRACE = {plane: {line: [tuple(e) for e in events]
                 for line, events in lines.items()}
         for plane, lines in json.loads(
             (pathlib.Path(__file__).parent / "recorded_trace.json")
             .read_text()).items()}
OPS = TRACE["/device:TPU:0"]["XLA Ops"]


def test_device_planes_are_picked_by_their_exact_prefix():
    assert list(tr.device_planes(TRACE)) == ["/device:TPU:0"]


def test_window_is_the_host_annotation():
    assert tr.window_of(TRACE, "bench.trace_window") == (500, 10500)


def test_busy_union_and_idle_share():
    # [1000, 5000) + [6000, 7000) + [7500, 10000) = 7500 ns of 10000.
    assert tr.busy_ns(OPS) == 7500
    s = tr.device_summary(TRACE, "bench.trace_window")
    assert s["window_s"] == pytest.approx(10000e-9)
    assert s["busy_s"] == pytest.approx(7500e-9)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(0.25)


def test_idle_gaps_longest_first_with_what_ran_before():
    gaps = tr.idle_gaps(OPS, 500, 10500)
    assert [(g[0], g[1]) for g in gaps] == [
        (5000, 1000), (500, 500), (7000, 500), (10000, 500)]
    assert gaps[0][2].startswith("%while.37")
    assert gaps[1][2] == "window start"
    assert gaps[2][2].startswith("%fusion.9")


def test_clip_cuts_events_to_the_window():
    assert tr.busy_ns(tr.clip(OPS, 4000, 6500)) == 1000 + 500


def test_module_times():
    m = tr.module_times(TRACE["/device:TPU:0"]["XLA Modules"])
    assert m["jit_decode_rounds"] == (pytest.approx(6500e-9), 2)
    assert m["jit_prefill_chunk_into_slot"] == (pytest.approx(1000e-9), 1)


def test_self_times_take_children_out_of_a_loop():
    top = dict(tr.top_ops(OPS))
    # copy-done 1000 + 2000 + 2000; the loops keep (4000 - 3500) + (2500 -
    # 2000); the names lose their layouts and operands.
    assert top["copy-done bf16[2560,16,8,128]"] == pytest.approx(5000e-9)
    assert top["while.37 (s32[]"] == pytest.approx(1000e-9)
    assert top["fusion.145 f32[64]"] == pytest.approx(500e-9)
    assert top["fusion.9 bf16[1,64,2048]"] == pytest.approx(1000e-9)
    assert list(top)[0] == "copy-done bf16[2560,16,8,128]"
