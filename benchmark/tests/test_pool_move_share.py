"""``programs.pool_move_share`` on the recording ``test_trace_spans.py``
explains (``recorded_spans.json``: a pool ``bf16[6,640,16,8,128]``).  Of
the two engine programs' 16000 ns of own time, two operations have a
result shaped like the pool or a plane of it: ``fusion.145
bf16[640,16,8,128]`` (the write into a plane sliced out, 9000-9500) and
``copy.37 bf16[6,640,16,8,128]`` (the pool copied whole, 14500-15000).
``fusion.144 bf16[2560,16,8,128]`` is sixteen slots' view, not a plane."""

import importlib.util
import pathlib

import pytest

HERE = pathlib.Path(__file__).parent


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


recording = _load("recording_of_spans", HERE / "test_trace_spans.py")
reader = _load("reader_pool_move_share",
               HERE.parent / "layer_metrics" / "programs.pool_move_share.py")


def _run(tmp_path, monkeypatch, **stats):
    run = recording._run(tmp_path, monkeypatch)
    run["counters"]["at_close"].update(stats)
    run["engine"] = {"kv_block_tokens": 16}
    run["config"] = {"num_hidden_layers": 6, "num_attention_heads": 16,
                     "num_key_value_heads": 8, "hidden_size": 2048}
    return run


def test_the_pool_shaped_operations_share(tmp_path, monkeypatch, capsys):
    run = _run(tmp_path, monkeypatch, kv_blocks=640, kv_planes=6)
    assert reader.read(run) == pytest.approx(100 * (500 + 500) / 16000)
    logged = capsys.readouterr().out
    assert "pool-shaped 0.0000 s copy.37 bf16[6,640,16,8,128]" in logged
    assert "pool-shaped 0.0000 s fusion.145 bf16[640,16,8,128]" in logged


def test_planes_come_from_the_configuration_where_stats_has_none(
        tmp_path, monkeypatch):
    """A program older than ``stats()["kv_planes"]``: layers x loop steps;
    the head size from the hidden size where the file states none."""
    run = _run(tmp_path, monkeypatch, kv_blocks=640)
    assert reader.pool_shapes(run) == {
        "6,640,16,8,128", "640,16,8,128", "1,640,16,8,128"}
    run["config"]["total_ut_steps"] = 4
    assert "24,640,16,8,128" in reader.pool_shapes(run)


def test_nothing_to_read_is_none_and_does_not_raise(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, kv_planes=6)      # no pool size
    assert reader.read(run) is None
    assert reader.read({"counters": {"at_close": {}}}) is None  # no trace
    run["counters"]["at_close"]["kv_blocks"] = 8
    assert reader.read(run) == 0.0          # a pool no operation moves


@pytest.mark.parametrize("name,dims", [
    ("%copy.68 = bf16[24,2560,16,8,128]{4,3,2,1,0:T(8,128)(2,1)} copy(bf16[24,"
     "2560,16,8,128]{4,3,2,1,0} %gte)", "24,2560,16,8,128"),
    ("%copy-done = s8[2560,16,8,128]{3,2,1,0} copy-done(%copy-start)",
     "2560,16,8,128"),
    ("%while.37 = (s32[]{:T(128)}, bf16[6,640,16,8,128]{4,3,2,1,0}) while(%t)",
     None),
    ("%copy-start.1 = (bf16[6,640,16,8,128]{4,3,2,1,0}, u32[]) copy-start(%p)",
     None),
    ("%constant.3 = s32[]{:T(128)} constant(0)", ""),
    ("an event with no type", None),
])
def test_result_dims(name, dims):
    assert reader.result_dims(name) == dims
