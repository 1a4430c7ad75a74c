"""Data pipeline tests: format roundtrip, native core vs python fallback,
shuffle, sharding, batching."""

import numpy as np
import pytest

from kubeflow_tpu.data.loader import (
    RecordDataset,
    RecordWriter,
    decode_example,
    encode_example,
    read_records,
    tensor_batches,
    write_example_shards,
    _native_lib,
)


@pytest.fixture(scope="module")
def shard_dir(tmp_path_factory):
    d = tmp_path_factory.mktemp("records")
    examples = [
        {"x": np.full((4,), i, np.float32), "y": np.int64(i)}
        for i in range(100)
    ]
    paths = write_example_shards(examples, d, examples_per_shard=25)
    return d, paths


class TestFormat:
    def test_roundtrip(self, tmp_path):
        p = tmp_path / "a.kftr"
        with RecordWriter(p) as w:
            w.write(b"hello")
            w.write(b"")
            w.write(b"\x00" * 1000)
        assert list(read_records(p)) == [b"hello", b"", b"\x00" * 1000]

    def test_bad_magic_rejected(self, tmp_path):
        p = tmp_path / "bad.bin"
        p.write_bytes(b"GARBAGE")
        with pytest.raises(IOError, match="magic"):
            list(read_records(p))

    def test_example_codec(self):
        ex = {"image": np.arange(12, dtype=np.float32).reshape(3, 4),
              "label": np.int64(7)}
        out = decode_example(encode_example(ex))
        np.testing.assert_array_equal(out["image"], ex["image"])
        assert out["label"] == 7


class TestNativeCore:
    def test_native_lib_builds(self):
        assert _native_lib() is not None, "g++ toolchain expected in image"

    def test_native_matches_python(self, shard_dir):
        _, paths = shard_dir
        native = sorted(RecordDataset(paths, num_threads=3))
        python = sorted(RecordDataset(paths, force_python=True))
        assert native == python
        assert len(native) == 100

    def test_shuffle_changes_order_keeps_multiset(self, shard_dir):
        _, paths = shard_dir
        plain = list(RecordDataset(paths, num_threads=1))
        shuffled = list(RecordDataset(paths, num_threads=1,
                                      shuffle_buffer=64, seed=7))
        assert sorted(plain) == sorted(shuffled)
        assert plain != shuffled

    def test_repeat(self, shard_dir):
        _, paths = shard_dir
        twice = list(RecordDataset([paths[0]], repeat=2))
        assert len(twice) == 50

    def test_error_surfaces(self, tmp_path):
        p = tmp_path / "trunc.kftr"
        with RecordWriter(p) as w:
            w.write(b"full record")
        # Truncate mid-payload.
        data = p.read_bytes()
        p.write_bytes(data[:-4])
        # Same IOError contract from both readers: the default (python
        # auto-select) and the explicitly threaded native core.
        with pytest.raises(IOError, match="truncated"):
            list(RecordDataset([p]))
        with pytest.raises(IOError, match="truncated"):
            list(RecordDataset([p], num_threads=1))


class TestSharding:
    def test_processes_partition_files(self, shard_dir):
        _, paths = shard_dir
        ds = RecordDataset(paths)
        seen = []
        for pid in range(2):
            seen += list(ds.shard(pid, 2))
        assert sorted(seen) == sorted(RecordDataset(paths))

    def test_too_few_files_raises(self, shard_dir):
        _, paths = shard_dir
        with pytest.raises(ValueError, match="no files"):
            RecordDataset([paths[0]]).shard(1, 2)


class TestTrainCnnFromShards:
    @pytest.mark.slow  # ~22s CNN train; the readers have direct tests above
    def test_train_cnn_reads_kftr(self, tmp_path):
        """train_cnn --data-dir: the full CNN entrypoint trains from KFTR
        shards through the loader (heir of tf_cnn_benchmarks' real-data
        mode, tf-controller-examples/tf-cnn/create_job_specs.py:98-119)."""
        from kubeflow_tpu.tools.train_cnn import main

        examples = [
            {"image": np.random.RandomState(i).randn(8, 8, 3).astype(
                np.float32),
             "label": np.int64(i % 4)}
            for i in range(64)
        ]
        write_example_shards(examples, tmp_path, examples_per_shard=16)
        rc = main([
            "--model", "resnet18", "--steps", "2",
            "--batch-size-per-device", "1", "--image-size", "8",
            "--num-classes", "4", "--dtype", "float32",
            "--data-dir", str(tmp_path), "--shuffle-buffer", "0",
            "--data-threads", "2", "--log-every", "1",
        ])
        assert rc == 0

    def test_train_cnn_no_shards_fails_cleanly(self, tmp_path):
        from kubeflow_tpu.tools.train_cnn import main

        assert main(["--steps", "1", "--data-dir", str(tmp_path)]) == 1


class TestLoaderThroughput:
    def test_native_core_keeps_up(self, tmp_path):
        """The native core exists to out-feed the chip; this smoke pins
        that it at least sustains multi-shard reads at a sane rate and
        does not regress below the single-thread python fallback on a
        parallel read."""
        import time

        payload = b"x" * 65536
        paths = []
        for s in range(4):
            p = tmp_path / f"{s}.kftr"
            with RecordWriter(p) as w:
                for _ in range(64):
                    w.write(payload)
            paths.append(p)

        def rate(**kw):
            t0 = time.perf_counter()
            n = sum(1 for _ in RecordDataset(paths, **kw))
            return n / (time.perf_counter() - t0)

        native = rate(num_threads=4)
        assert rate(force_python=True) > 0  # fallback functional
        assert native > 1000, f"native core too slow: {native:.0f} rec/s"


class TestBatching:
    def test_trainer_shaped_batches(self, shard_dir):
        _, paths = shard_dir
        batches = list(tensor_batches(RecordDataset(paths), 32))
        assert len(batches) == 3  # 100 // 32, remainder dropped
        assert batches[0]["x"].shape == (32, 4)
        assert batches[0]["y"].shape == (32,)

    def test_keep_remainder(self, shard_dir):
        _, paths = shard_dir
        batches = list(tensor_batches(RecordDataset(paths), 32,
                                      drop_remainder=False))
        assert batches[-1]["x"].shape == (4, 4)


class TestStackedBatches:
    """In-core decode + batch assembly (loader.stacked_batches): the
    pipeline default, where the C++ core fills per-key batch buffers
    numpy wraps zero-copy."""

    def test_matches_python_pipeline_exactly(self, shard_dir):
        _, paths = shard_dir
        # num_threads=1 => deterministic file/record order, comparable
        # element-for-element with the sequential python path.
        nat = list(RecordDataset(paths, num_threads=1)
                   .stacked_batches(32))
        py = list(tensor_batches(
            RecordDataset(paths, force_python=True), 32))
        assert len(nat) == len(py) == 3
        for a, b in zip(nat, py):
            assert set(a) == set(b)
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])
                assert a[k].dtype == b[k].dtype

    def test_threaded_same_multiset(self, shard_dir):
        _, paths = shard_dir
        nat = list(RecordDataset(paths, num_threads=4)
                   .stacked_batches(10, drop_remainder=False))
        ys = np.sort(np.concatenate([b["y"] for b in nat]))
        py = list(tensor_batches(
            RecordDataset(paths, force_python=True), 10,
            drop_remainder=False))
        ys_py = np.sort(np.concatenate([b["y"] for b in py]))
        np.testing.assert_array_equal(ys, ys_py)

    def test_remainder(self, shard_dir):
        _, paths = shard_dir
        nat = list(RecordDataset(paths, num_threads=1)
                   .stacked_batches(32, drop_remainder=False))
        assert [b["y"].shape[0] for b in nat] == [32, 32, 32, 4]

    def test_schema_mismatch_raises(self, tmp_path):
        from kubeflow_tpu.data.loader import RecordWriter, encode_example

        p = tmp_path / "mixed.kftr"
        with RecordWriter(p) as w:
            w.write(encode_example({"x": np.zeros(4, np.float32)}))
            w.write(encode_example({"x": np.zeros(5, np.float32)}))
        with pytest.raises(IOError, match="schema"):
            list(RecordDataset([p]).stacked_batches(2))

    def test_non_kte1_payload_falls_back(self, tmp_path):
        from kubeflow_tpu.data.loader import RecordWriter

        import io as _io

        p = tmp_path / "npz.kftr"
        buf = _io.BytesIO()
        np.savez(buf, x=np.arange(4, dtype=np.float32))
        with RecordWriter(p) as w:
            for _ in range(4):
                w.write(buf.getvalue())
        batches = list(RecordDataset([p]).stacked_batches(2))
        assert len(batches) == 2
        assert batches[0]["x"].shape == (2, 4)

    def test_uint8_dtype_roundtrips(self, tmp_path):
        """1-byte dtypes serialize as '|u1' — the '|' must not break
        schema parsing (uint8 images are the serving wire format)."""
        from kubeflow_tpu.data.loader import write_example_shards

        img = np.arange(48, dtype=np.uint8).reshape(4, 4, 3)
        paths = write_example_shards(
            ({"image": img + i, "ok": np.bool_(i % 2)} for i in range(6)),
            tmp_path, examples_per_shard=6)
        (batch,) = RecordDataset(paths, num_threads=1).stacked_batches(6)
        assert batch["image"].dtype == np.uint8
        assert batch["ok"].dtype == np.bool_
        np.testing.assert_array_equal(batch["image"][2], img + 2)

    def test_scalar_fields_stack_to_vector(self, tmp_path):
        from kubeflow_tpu.data.loader import write_example_shards

        paths = write_example_shards(
            ({"label": np.int64(i)} for i in range(8)),
            tmp_path, examples_per_shard=8)
        (batch,) = RecordDataset(paths, num_threads=1).stacked_batches(8)
        np.testing.assert_array_equal(batch["label"], np.arange(8))

    def test_truncated_shard_raises_not_truncates(self, tmp_path):
        """A corrupt shard must raise from the stacked path exactly as
        it does from raw iteration — silent short batches would train
        on partial data (review finding r3)."""
        from kubeflow_tpu.data.loader import RecordWriter, encode_example

        p = tmp_path / "trunc.kftr"
        with RecordWriter(p) as w:
            for i in range(64):
                w.write(encode_example({"x": np.full(8, i, np.float32)}))
        data = p.read_bytes()
        p.write_bytes(data[:-7])  # cut mid-payload
        with pytest.raises(IOError, match="truncated"):
            list(RecordDataset([p], num_threads=1).stacked_batches(64))

    def test_nbytes_shape_mismatch_rejected(self, tmp_path):
        """A record whose nbytes disagrees with shape x dtype must be
        rejected at schema lock-in — the fill path sizes buffers from
        shape x dtype and copies nbytes (heap overflow otherwise)."""
        import struct as st

        from kubeflow_tpu.data.loader import RecordWriter

        # Hand-craft KTE1: key 'x', dtype '<f4', shape (4,), but
        # nbytes=64 with 64 payload bytes (parse succeeds, sizes lie).
        payload = (b"KTE1" + st.pack("<H", 1)
                   + st.pack("<HH", 1, 3) + b"x" + b"<f4"
                   + st.pack("<B", 1) + st.pack("<q", 4)
                   + st.pack("<Q", 64) + b"\0" * 64)
        p = tmp_path / "evil.kftr"
        with RecordWriter(p) as w:
            for _ in range(4):
                w.write(payload)
        with pytest.raises((IOError, ValueError)):
            list(RecordDataset([p], num_threads=1).stacked_batches(4))

    def test_reserved_key_characters_rejected_at_encode(self):
        from kubeflow_tpu.data.loader import encode_example

        with pytest.raises(ValueError, match="reserved"):
            encode_example({"a|b": np.zeros(2, np.float32)})
        with pytest.raises(ValueError, match="reserved"):
            encode_example({"a;b": np.zeros(2, np.float32)})

    def test_foreign_shard_with_separator_key_falls_back(self, tmp_path):
        """A shard written by a foreign producer with a '|' in a key:
        the native schema path refuses it and stacked_batches falls back
        to the python decode loop, which handles it."""
        import struct as st

        from kubeflow_tpu.data.loader import RecordWriter

        arr = np.arange(4, dtype=np.float32)
        payload = (b"KTE1" + st.pack("<H", 1)
                   + st.pack("<HH", 3, 3) + b"a|b" + b"<f4"
                   + st.pack("<B", 1) + st.pack("<q", 4)
                   + st.pack("<Q", 16) + arr.tobytes())
        p = tmp_path / "foreign.kftr"
        with RecordWriter(p) as w:
            for _ in range(4):
                w.write(payload)
        (batch,) = RecordDataset([p]).stacked_batches(4)
        assert batch["a|b"].shape == (4, 4)
        np.testing.assert_array_equal(batch["a|b"][0], arr)

    def test_shuffle_composes(self, shard_dir):
        _, paths = shard_dir
        nat = list(RecordDataset(paths, num_threads=1, shuffle_buffer=64,
                                 seed=3).stacked_batches(
                                     10, drop_remainder=False))
        plain = list(RecordDataset(paths, num_threads=1)
                     .stacked_batches(10, drop_remainder=False))
        ys = np.concatenate([b["y"] for b in nat])
        ys_plain = np.concatenate([b["y"] for b in plain])
        assert not np.array_equal(ys, ys_plain)
        np.testing.assert_array_equal(np.sort(ys), np.sort(ys_plain))


class TestSeekResume:
    """tensor_batches.seek — the resume fast-path Trainer.fit probes
    for: a decode-free header-walk skip for unshuffled record datasets
    (the reference's era had no resume at all; fit's contract is
    'rerun the same command')."""

    def test_seek_matches_slicing(self, shard_dir):
        # force_python: the fast header-walk skip applies only to the
        # file-ordered python reader (the threaded native core
        # interleaves files, so native datasets drain on seek).
        _, paths = shard_dir  # 100 examples over 4 files of 25
        full = list(tensor_batches(
            RecordDataset(paths, force_python=True), 8))
        for n in (0, 1, 3, 7):  # incl. skips crossing file boundaries
            it = tensor_batches(
                RecordDataset(paths, force_python=True), 8)
            it.seek(n)
            got = list(it)
            assert len(got) == len(full) - n, (n, len(got))
            np.testing.assert_array_equal(got[0]["x"], full[n]["x"])
            np.testing.assert_array_equal(got[-1]["y"], full[-1]["y"])

    def test_seek_across_epochs(self, shard_dir):
        _, paths = shard_dir
        full = list(tensor_batches(
            RecordDataset(paths, repeat=2, force_python=True), 8))
        it = tensor_batches(
            RecordDataset(paths, repeat=2, force_python=True), 8)
        it.seek(13)  # crosses into the second epoch
        got = list(it)
        np.testing.assert_array_equal(got[0]["x"], full[13]["x"])

    def test_seek_past_end_yields_nothing(self, shard_dir):
        _, paths = shard_dir
        it = tensor_batches(RecordDataset(paths, force_python=True), 8)
        it.seek(999)
        assert list(it) == []

    def test_native_dataset_seek_drains_consistently(self, shard_dir):
        """Native (threaded) datasets drain on seek; the resumed stream
        must still be the same LENGTH as a slice (content order is the
        native core's own)."""
        _, paths = shard_dir
        full = list(tensor_batches(RecordDataset(paths), 8))
        it = tensor_batches(RecordDataset(paths), 8)
        it.seek(5)
        assert len(list(it)) == len(full) - 5

    def test_shuffled_dataset_falls_back_to_drain(self, shard_dir):
        _, paths = shard_dir
        ds = RecordDataset(paths, shuffle_buffer=16, force_python=True)
        full = list(tensor_batches(
            RecordDataset(paths, shuffle_buffer=16, force_python=True),
            8))
        it = tensor_batches(ds, 8)
        it.seek(2)
        got = list(it)
        # Same shuffle seed: drain-skip reproduces the same stream.
        assert len(got) == len(full) - 2
        np.testing.assert_array_equal(got[0]["x"], full[2]["x"])

    def test_fit_uses_seek_on_resume(self, shard_dir, tmp_path):
        """End to end: Trainer.fit resumes from a checkpoint and seeks
        the dataset instead of replaying decoded batches."""
        import jax
        import jax.numpy as jnp
        import optax

        from kubeflow_tpu.parallel import MeshSpec
        from kubeflow_tpu.runtime.checkpoint import CheckpointManager
        from kubeflow_tpu.runtime.metrics import MetricsLogger
        from kubeflow_tpu.runtime.train import Trainer

        _, paths = shard_dir

        def init_fn(rng):
            return {"w": jnp.zeros((4,))}, {}

        def loss_fn(params, mutable, batch, rng):
            pred = batch["x"].astype(jnp.float32) @ params["w"]
            loss = jnp.mean((pred - batch["y"].astype(jnp.float32)) ** 2)
            return loss, ({}, {})

        def make_trainer():
            return Trainer(
                init_fn=init_fn, loss_fn=loss_fn, tx=optax.sgd(1e-3),
                mesh=MeshSpec(data=1).build(jax.devices()[:1]),
                checkpoints=CheckpointManager(str(tmp_path / "ck")),
                checkpoint_every=4,
                metrics=MetricsLogger(stream=open("/dev/null", "w")),
            )

        t1 = make_trainer()
        t1.fit(tensor_batches(RecordDataset(paths), 8), num_steps=4,
               log_every=0)
        # Second run resumes at step 4; seek must be the path taken.
        seeks = []
        data = tensor_batches(RecordDataset(paths), 8)
        orig_seek = data.seek
        data.seek = lambda n: (seeks.append(n), orig_seek(n))[1]
        t2 = make_trainer()
        t2.fit(data, num_steps=8, log_every=0)
        assert seeks == [4], seeks


class TestTransientRetry:
    """data.next hook: transient read errors retry with capped jittered
    backoff on the policy clock; budget exhaustion raises DataError."""

    def test_injected_faults_retried_to_success(self, shard_dir):
        from kubeflow_tpu.data.loader import DataError  # noqa: F401
        from kubeflow_tpu.testing import faults

        _, paths = shard_dir
        ds = RecordDataset(paths, force_python=True)
        want = [b["y"].tolist() for b in tensor_batches(ds, 10)]
        with faults.injected(
                "data.next:raise*3;data.next:skew=100"):
            got = [b["y"].tolist()
                   for b in tensor_batches(ds, 10, retries=4)]
        assert got == want  # stream re-aligned past yielded batches

    def test_mid_stream_fault_does_not_duplicate_batches(
            self, shard_dir):
        from kubeflow_tpu.testing import faults

        _, paths = shard_dir
        ds = RecordDataset(paths, force_python=True)
        want = [b["y"].tolist() for b in tensor_batches(ds, 10)]
        # Fault fires on the 4th pull only (3 clean encounters first,
        # via times-bounded skew entries consuming nothing).
        with faults.injected("seed=1;data.next:raise=0*1@0.35;"
                             "data.next:skew=100"):
            got = [b["y"].tolist()
                   for b in tensor_batches(ds, 10, retries=4)]
        assert got == want

    def test_budget_exhaustion_raises_typed_error(self, shard_dir):
        from kubeflow_tpu.data.loader import DataError
        from kubeflow_tpu.testing import faults

        _, paths = shard_dir
        ds = RecordDataset(paths, force_python=True)
        with faults.injected("data.next:raise;data.next:skew=100"):
            with pytest.raises(DataError) as exc:
                list(tensor_batches(ds, 10, retries=2))
        assert isinstance(exc.value.__cause__, faults.FaultInjected)

    def test_real_io_error_is_transient(self, tmp_path):
        """A shard that becomes readable between attempts (flaky
        mount) recovers without DataError."""
        from kubeflow_tpu.testing import faults

        examples = [{"x": np.full((2,), i, np.int32)}
                    for i in range(8)]
        paths = write_example_shards(examples, tmp_path,
                                     examples_per_shard=8)
        good = paths[0].read_bytes()
        paths[0].write_bytes(good[:9])  # truncated: IOError on read
        ds = RecordDataset(paths, force_python=True)
        tb = tensor_batches(ds, 4, retries=3)
        orig_wait = tb._retry_wait

        def heal_then_wait(attempt):
            paths[0].write_bytes(good)  # the mount comes back
            orig_wait(attempt)

        tb._retry_wait = heal_then_wait
        with faults.injected("data.next:skew=100"):
            out = list(tb)
        total = sum(b["x"].shape[0] for b in out)
        assert total == 8
        assert [b["x"][0, 0] for b in out] == [0, 4]  # no duplicates

    def test_retry_budget_is_consecutive(self, shard_dir):
        """A success resets the budget: N scattered faults with budget
        < N still complete."""
        from kubeflow_tpu.testing import faults

        _, paths = shard_dir
        ds = RecordDataset(paths, force_python=True)
        want = [b["y"].tolist() for b in tensor_batches(ds, 10)]
        with faults.injected("seed=3;data.next:raise@0.3;"
                             "data.next:skew=100"):
            got = [b["y"].tolist()
                   for b in tensor_batches(ds, 10, retries=2)]
        assert got == want

    def test_one_shot_iterable_propagates_raw(self, shard_dir):
        """A plain generator dataset cannot be rebuilt+realigned —
        the fault propagates unretried (no silent batch drops); the
        supervisor's per-attempt data_factory owns recovery there."""
        from kubeflow_tpu.testing import faults

        _, paths = shard_dir
        payloads = list(RecordDataset(paths, force_python=True))

        def gen():
            yield from payloads

        with faults.injected("data.next:raise*1"):
            with pytest.raises(faults.FaultInjected):
                list(tensor_batches(gen(), 10, retries=5))
