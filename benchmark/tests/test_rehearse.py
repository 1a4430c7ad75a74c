"""``run.py --rehearse`` end to end at tiny shapes on the CPU, for every cell
of the manifest; the correctness check's control at a size a test can hold;
and a run whose timed path is broken underneath, which has to come out as
not correct.  These drive everything of a run except the look for a chip."""

import importlib.util
import json
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in MANIFEST["workloads"]]


@pytest.fixture(scope="module")
def run_py():
    spec = importlib.util.spec_from_file_location(
        "bench_run_py", ROOT / "benchmark" / "run.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def rehearse(run_py, capsys, cell, *extra, seed=2**31 + 77, trace=0):
    capsys.readouterr()
    assert run_py.main(["--workload", cell, "--seed", str(seed), "--seconds",
                        "3", "--trace", str(trace), "--rehearse", *extra]) == 0
    out = capsys.readouterr().out
    compared = {}
    for line in out.splitlines():
        if line.startswith("compared "):
            name, _, rest = line[len("compared "):].partition(" = ")
            compared[name] = float(rest.split(" limit ")[0])
    return json.loads(out.strip().splitlines()[-1]), compared


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_and_is_sound(run_py, capsys, cell, trace):
    line, compared = rehearse(run_py, capsys, cell, trace=trace)
    # A CPU run reports no metric and never "correct": true.
    assert line["correct"] is False and line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert "memory_peak_bytes" not in line["device"]
    assert line["rehearsal"]["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert compared["requests_with_wrong_token_count"] == 0
    which = "per_layer" if trace else "end_to_end"
    wanted = {m["name"] for m in MANIFEST[which]
              if cell in m.get("workloads", CELLS)}
    found = set(line["rehearsal"]["metrics"])
    assert found <= wanted
    if not trace:
        assert found == wanted


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_control_in_lower_precision_reads_worse(seed):
    """The control of the correctness check at a size a test can hold: the
    reference with int8 weights in the program's place.  The token it puts
    first lies measurably below the float32 reference's best, where the
    reference's own first token lies at 0.  (At the cells' own sizes the
    program and the control were read on the chip, PERF.md section 2; each
    limit sits between the two readings.)"""
    import numpy as np

    from benchmark.lib import reference

    c = dict(hidden_size=256, num_hidden_layers=4, num_attention_heads=8,
             num_key_value_heads=2, head_dim=32, intermediate_size=512,
             vocab_size=4096, rope_theta=1e6, rms_norm_eps=1e-5,
             tie_word_embeddings=False)
    tokens = np.random.default_rng(seed).integers(1, 4096, 256,
                                                  dtype=np.int32)
    ref = np.asarray(reference.Reference(c, seed).logits(tokens, 0, 256, 256))
    low = np.asarray(reference.Reference(c, seed, quantize="int8").logits(
        tokens, 0, 256, 256))
    sound = reference.served_gaps(ref, ref.argmax(-1))
    control = reference.served_gaps(ref, low.argmax(-1))
    assert sound.max() == 0.0
    assert control.mean() > 0.001 and (control > 0).mean() > 0.03


@pytest.mark.parametrize("cell", CELLS)
def test_a_token_altered_where_it_is_produced_is_not_correct(
        run_py, capsys, cell, monkeypatch):
    from kubeflow_tpu.serving.engine import DecodeEngine

    real = DecodeEngine.submit_stream

    def altered(self, inputs, deadline=None):
        meta, stream = real(self, inputs, deadline)

        def wrong():
            for chunk in stream:
                yield [(t + 1) % 512 or 1 for t in chunk]

        return meta, wrong()

    monkeypatch.setattr(DecodeEngine, "submit_stream", altered)
    line, compared = rehearse(run_py, capsys, cell)
    assert line["rehearsal"]["correct"] is False
    assert compared["requests_with_wrong_token_count"] == 0
    assert compared["served_logit_gap_mean"] > 0.5


def test_a_request_cut_short_is_not_correct(run_py, capsys, monkeypatch):
    from kubeflow_tpu.serving.engine import DecodeEngine

    real = DecodeEngine.submit_stream

    def short(self, inputs, deadline=None):
        meta, stream = real(self, inputs, deadline)
        return meta, (c[:1] for i, c in enumerate(stream) if i == 0)

    monkeypatch.setattr(DecodeEngine, "submit_stream", short)
    with pytest.raises(SystemExit):  # set-up traffic already sees it
        rehearse(run_py, capsys, CELLS[0])


def test_without_a_chip_there_is_no_result(run_py, capsys):
    with pytest.raises(SystemExit) as e:
        run_py.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                     "--trace", "0"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out.strip() == ""
