"""bench.py's output and exit-code contract: one JSON line on stdout that
fits a 2000-character tail, a soft deadline that skips the tail of the
nested sub-benches instead of losing the headline, and a failing exit
code whenever the capture is not whole (no backend, a failed sub-bench).
"""

import json
import os

import bench


def _fake_both(monkeypatch, sub_benches):
    """``bench.py --model both --fake-devices 8`` with the ResNet
    headline stubbed and every other sub-bench replaced by
    ``sub_benches`` (name -> callable)."""
    import sys as _sys

    # main() appends the fake-device flag to XLA_FLAGS in-place; pin the
    # var so the append is rolled back after the test (subprocess-
    # spawning tests inherit os.environ).
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    monkeypatch.setattr(_sys, "argv", ["bench.py", "--model", "both",
                                       "--fake-devices", "8"])
    headline = {"metric": "resnet50_images_per_sec_per_chip",
                "value": 1.0, "unit": "x", "vs_baseline": 0.0}
    monkeypatch.setattr(bench, "bench_resnet",
                        lambda *a, **k: dict(headline, detail={}))
    for name, fn in sub_benches.items():
        monkeypatch.setattr(bench, name, fn)


_SUB_BENCHES = ("bench_lm", "bench_serving", "bench_lm_decode",
                "bench_lm_engine", "bench_data", "bench_hfta",
                "bench_colocation")


def test_no_backend_is_exit_code_1(monkeypatch, capsys):
    """A capture with no device is not a result: one parseable failure
    record on stdout, and a non-zero exit code."""
    import jax

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    _fake_both(monkeypatch, {})
    monkeypatch.setattr(jax, "devices", no_backend)
    assert bench.main() == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    record = json.loads(out[0])
    assert record["metric"] == "backend_init_failed"
    assert "Unable to initialize" in record["detail"]["error"]


def test_failed_sub_bench_is_recorded_and_fails_the_exit_code(
        monkeypatch, capsys):
    """One sub-bench raising must not stop the ones after it — and must
    not pass for a whole capture either: the JSON names it, rc != 0."""
    ran = []

    def ok(name):
        def run(*a, **k):
            ran.append(name)
            return {"metric": name, "value": 1.0, "unit": "x",
                    "vs_baseline": 0.0, "detail": {
                        "step_time_ms": 1, "mfu": None, "seq_len": 8,
                        "attention": "dot", "moe_experts": 4,
                        "optimizer": "adafactor"}}
        return run

    def boom(*a, **k):
        raise ValueError("engine fell over")

    monkeypatch.setenv("KFT_BENCH_DEADLINE_S", "0")
    _fake_both(monkeypatch, {**{n: ok(n) for n in _SUB_BENCHES},
                             "bench_lm_engine": boom})
    assert bench.main() == 1
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, out
    record = json.loads(out[0])
    assert record["detail"]["failed_sub_benches"] == {
        "lm_engine": "ValueError: engine fell over"}
    # everything after the failure still ran
    assert {"bench_data", "bench_hfta", "bench_colocation"} <= set(ran)


def test_soft_deadline_skips_tail_but_prints_headline(monkeypatch, capsys):
    """A caller's hard timeout mid-suite records NOTHING (the one JSON
    line prints at the end); the soft deadline must skip remaining
    sub-benches and still deliver the headline record."""
    def boom(*a, **k):
        raise AssertionError("sub-bench ran past the deadline")

    monkeypatch.setenv("KFT_BENCH_DEADLINE_S", "0.000001")
    _fake_both(monkeypatch, {n: boom for n in _SUB_BENCHES})
    assert bench.main() == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1, out
    record = json.loads(out[0])
    assert record["metric"] == "resnet50_images_per_sec_per_chip"
    assert set(record["detail"]["skipped_sub_benches"]) == {
        "lm", "lm_moe", "serving", "lm_decode", "lm_decode_int8",
        "lm_engine", "data", "hfta", "colocation"}


def _both_result():
    """A --model=both record as one v5e chip produced it."""
    return {
        "metric": "resnet50_images_per_sec_per_chip", "value": 411.2,
        "unit": "images/sec/chip", "vs_baseline": 0.8,
        "detail": {
            "images_per_sec": 411.2, "step_time_ms": 218.0, "mfu": 0.34,
            "device": "TPU v5 lite",
            "roofline": {"frac_of_roofline": 0.91},
            "lm": {"value": 38000, "mfu": 0.55, "seq_len": 2048,
                   "step_time_ms": 430, "attention": "flash"},
            "lm_moe": {"value": 41000, "mfu": 0.432, "seq_len": 2048,
                       "moe_experts": 4, "optimizer": "adafactor"},
            "serving": {
                "sustained_ms_per_request": 1.41,
                "batcher_capacity_requests_per_sec": 142.6,
                "batcher_small_image": {"requests_per_sec": 482.4},
                # ballast standing in for the fields that overflowed
                # the driver tail in round 4
                "batcher_batch_size_hist": {str(i): i for i in range(64)},
            },
            "lm_decode": {"batched_tokens_per_sec": 3479.5,
                          "filler": "x" * 1200},
            "lm_decode_int8": {"batched_tokens_per_sec": 4058.0},
            "data": {"pipeline_native_examples_per_sec": 63962.0,
                     "native_vs_python_ratio": 1.77},
        },
    }


def test_headline_summary_fits_driver_tail():
    """A reader that keeps only the last 2000 characters of stdout must
    still get a parseable line: the summary must carry every north-star
    metric and fit with room to spare."""
    summary = bench.headline_summary(_both_result())
    line = json.dumps(summary)
    assert len(line) < 1500
    d = summary["detail"]
    assert summary["value"] == 411.2
    assert d["resnet_mfu"] == 0.34
    assert d["resnet_roofline_frac"] == 0.91
    assert d["lm_mfu"] == 0.55
    assert d["moe_mfu"] == 0.432
    assert d["decode_tokens_per_sec"] == 3479.5
    assert d["decode_tokens_per_sec_int8"] == 4058.0
    assert d["serving_batcher_capacity_req_s"] == 142.6
    assert d["serving_small_image_req_s"] == 482.4
    assert d["data_native_vs_python"] == 1.77
    assert d["full_results"] == "artifacts/bench_full.json"


def test_emit_big_record_compacts_stdout_keeps_full_blob(
        tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    result = _both_result()
    bench.emit(result)
    captured = capsys.readouterr()
    lines = captured.out.strip().splitlines()
    assert len(lines) == 1
    assert len(lines[0]) < 2000
    assert json.loads(lines[0])["detail"]["moe_mfu"] == 0.432
    full = json.loads((tmp_path / "artifacts/bench_full.json").read_text())
    assert full == result
    assert "FULL RESULT:" in captured.err


def test_emit_big_single_model_record_keeps_scalar_detail(
        tmp_path, monkeypatch, capsys):
    """A large --model=serving record is NOT both-shaped; emit must keep
    its scalar metrics on stdout and drop only the oversized values."""
    monkeypatch.chdir(tmp_path)
    record = {
        "metric": "serving_predict_sustained_ms", "value": 1.4,
        "unit": "ms/request", "detail": {
            "batcher_capacity_requests_per_sec": 173.5,
            "wire_ceiling_req_s": 204.2,
            "device_ms_per_batch16": 0.26,
            "batcher_batch_size_hist": {str(i): i for i in range(400)},
        },
    }
    bench.emit(record)
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 1 and len(lines[0]) < 2000
    d = json.loads(lines[0])["detail"]
    assert d["batcher_capacity_requests_per_sec"] == 173.5
    assert d["wire_ceiling_req_s"] == 204.2
    assert d["device_ms_per_batch16"] == 0.26
    assert d["truncated_keys"] == ["batcher_batch_size_hist"]
    assert d["full_results"] == "artifacts/bench_full.json"


def test_emit_small_record_passes_through(tmp_path, monkeypatch, capsys):
    monkeypatch.chdir(tmp_path)
    record = {"metric": "m", "value": 1.0, "unit": "x", "vs_baseline": 0.0,
              "detail": {}}
    bench.emit(record)
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1
    assert json.loads(out[0]) == record
