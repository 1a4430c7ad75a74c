"""Benchmark entrypoint — prints ONE JSON line on stdout.

Measures the framework's heirs of the reference's headline benchmark
harness (tf_cnn_benchmarks, kubeflow/tf-job/prototypes/
tf-cnn-benchmarks.jsonnet:7).  The reference published no absolute
numbers, so ``vs_baseline`` reports achieved MFU relative to the
BASELINE.json north-star of 50% MFU.

Training workloads are measured through Trainer.fit (the shipped loop IS
the benchmarked loop):
  --model=resnet   ResNet-50 images/sec (the reference's headline).
  --model=lm       Transformer LM tokens/sec with the Pallas flash
                   attention kernel — the long-context capability the
                   reference never had.
  --model=serving  predict p50/p99 + micro-batcher throughput (the
                   reference published only a correctness golden).
  --model=fleet    router-hop overhead vs direct single-replica p50 +
                   delivered tok/s through the fleet router at 1 -> 3
                   replicas.
  --model=data     KFTR input pipeline examples/sec, native vs python.
  --model=both     ResNet headline with the others nested in detail.

Runs on whatever devices JAX sees: the attached TPU chip, or a fake CPU
slice with --fake-devices N for hermetic testing.  Diagnostics go to
stderr; stdout carries exactly the one JSON line.  Exits non-zero when no
backend comes up or a nested sub-benchmark failed.
"""

from __future__ import annotations

import argparse
import json
import math
import sys
import time


def closed_loop_clients(batcher, make_inputs, n_clients, per_client):
    """Drive a MicroBatcher with closed-loop client threads.

    Returns (requests_per_sec, stats, n_failures): failed submits are
    counted, not silently folded into throughput — both the serving and
    lm-decode benches report through this one loop.
    """
    import threading

    failures = []

    def client():
        for _ in range(per_client):
            try:
                batcher.submit(make_inputs())
            except Exception as exc:  # noqa: BLE001 — recorded, reported
                failures.append(exc)

    threads = [threading.Thread(target=client) for _ in range(n_clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    stats = batcher.stats()
    ok = n_clients * per_client - len(failures)
    return ok / wall, stats, len(failures)


def measure_fit(trainer, state, dev_batch, warmup: int, steps: int,
                steps_per_call: int = 1):
    """Run Trainer.fit twice (compile+warmup, then measured) and return the
    steady-state step time from the final metrics window.

    The batch is staged to HBM once and the iterator repeats it (fit's
    shard_batch device_put is then a no-op), so the number measures device
    step throughput, not host->device bandwidth.
    ``steps_per_call`` engages fit's host-loop fusion (k steps per
    dispatch), amortizing per-dispatch host overhead; warmup runs at
    least one fused call so the scan program compiles outside the window.
    The measured fit logs exactly once, at its end: the recorded
    step_time is wall/steps for the whole window, closed by one real
    metrics read.
    """
    import jax  # noqa: F401  (import order: caller configured platform)

    def repeat(b):
        while True:
            yield b

    k = max(1, steps_per_call)
    # Warm both programs the measured fit will use: the fused k-step
    # scan, plus the single-step remainder program when steps % k != 0
    # (otherwise its first compile would land inside the timed window).
    # The warmup fit only runs single steps for its own warm % k tail,
    # so warm itself must not be a multiple of k in that case.
    warm = max(warmup, k)
    if steps % k and warm % k == 0:
        warm += 1
    state = trainer.fit(
        repeat(dev_batch), warm, state=state,
        examples_per_step=0, log_every=warm, steps_per_call=k,
    )
    t0 = time.perf_counter()
    state = trainer.fit(
        repeat(dev_batch), steps, state=state,
        examples_per_step=0, log_every=steps, steps_per_call=k,
    )
    print(f"measured fit wall: {time.perf_counter()-t0:.2f} s",
          file=sys.stderr)
    rec = trainer.metrics.history[-1]
    return rec["step_time_s"]


def bench_resnet(args, devices, n_chips, on_tpu):
    import numpy as np
    import optax

    from kubeflow_tpu.models.classification import classification_task
    from kubeflow_tpu.models.resnet import ResNetConfig
    from kubeflow_tpu.parallel import MeshSpec
    from kubeflow_tpu.runtime.metrics import MetricsLogger, mfu, peak_flops
    from kubeflow_tpu.runtime.train import Trainer

    batch = args.batch or (256 if on_tpu else 64) * n_chips
    size = args.image_size
    print(
        f"bench: resnet50 train step, {n_chips}x{devices[0].device_kind}, "
        f"global batch {batch}, image {size}",
        file=sys.stderr,
    )
    peak = peak_flops(devices[0])
    cfg = ResNetConfig(name="resnet50")
    model = cfg.build()
    init_fn, loss_fn = classification_task(model, (1, size, size, 3))
    mesh = MeshSpec(data=n_chips).build(devices)
    trainer = Trainer(
        init_fn=init_fn, loss_fn=loss_fn,
        tx=optax.sgd(0.1, momentum=0.9), mesh=mesh,
        metrics=MetricsLogger(stream=sys.stderr),
        flops_per_example=cfg.fwd_flops_per_image * (size / 224) ** 2,
        peak_flops_per_chip=peak,
    )
    state = trainer.create_state()
    rng = np.random.RandomState(0)
    host_batch = {
        "image": rng.randn(batch, size, size, 3).astype(np.float32),
        "label": rng.randint(0, 1000, size=(batch,)),
    }
    dev_batch = trainer.shard_batch(host_batch)

    # Roofline context: the v5e ResNet step is HBM-bandwidth-bound, not
    # MXU-bound — report how close to the chip's own ceiling we run.
    roofline = {}
    if on_tpu:  # the roofline is the chip's; a CPU has none
        try:
            ca = trainer.compile_step().lower(state, dev_batch).compile() \
                .cost_analysis()
            hbm_gbps = {"v5p": 2765e9, "v6e": 1640e9}.get(
                next((g for g in ("v5p", "v6e")
                      if g in devices[0].device_kind.lower()), ""), 819e9)
            flops_ms = ca.get("flops", 0) / (peak * n_chips) * 1e3
            bytes_ms = ca.get("bytes accessed", 0) / (hbm_gbps * n_chips) * 1e3
            roofline = {
                "hlo_flops": ca.get("flops", 0),
                "hlo_bytes_accessed": ca.get("bytes accessed", 0),
                "mxu_bound_ms": round(flops_ms, 2),
                "hbm_bound_ms": round(bytes_ms, 2),
            }
        except Exception as e:  # cost analysis is best-effort
            print(f"cost_analysis unavailable: {e}", file=sys.stderr)

    step_s = measure_fit(trainer, state, dev_batch, args.warmup,
                         args.steps, steps_per_call=args.steps_per_call)
    print(f"steady state: {step_s*1e3:.2f} ms/step", file=sys.stderr)
    images_per_sec = batch / step_s
    flops_per_step = 3 * cfg.fwd_flops_per_image * batch * (size / 224) ** 2
    # No peak off-TPU (runtime.metrics.peak_flops): no utilization either.
    achieved_mfu = (mfu(flops_per_step, step_s, n_chips, peak)
                    if peak else None)
    if roofline:
        bound_ms = max(roofline["mxu_bound_ms"], roofline["hbm_bound_ms"])
        if bound_ms:
            roofline["frac_of_roofline"] = round(
                bound_ms / (step_s * 1e3), 4)
    return {
        "metric": "resnet50_images_per_sec_per_chip",
        "value": round(images_per_sec / n_chips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": achieved_mfu and round(achieved_mfu / 0.50, 4),
        "detail": {
            "images_per_sec": round(images_per_sec, 2),
            "step_time_ms": round(step_s * 1e3, 2),
            "global_batch": batch,
            "n_chips": n_chips,
            "mfu": achieved_mfu and round(achieved_mfu, 4),
            "device": devices[0].device_kind,
            "roofline": roofline,
        },
    }


def bench_lm(args, devices, n_chips, on_tpu):
    """Transformer LM with flash attention: tokens/sec/chip + MFU."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubeflow_tpu.models.transformer import TransformerConfig, lm_task
    from kubeflow_tpu.parallel import MeshSpec
    from kubeflow_tpu.runtime.metrics import MetricsLogger, mfu, peak_flops
    from kubeflow_tpu.runtime.train import Trainer

    seq = args.seq_len if on_tpu else min(args.seq_len, 128)
    # Size presets (per-chip batch chosen to fit v5e HBM with the
    # memory-minimal remat policy).
    sizes = {
        "188m": dict(d_model=1024, n_layers=12, n_heads=8, n_kv_heads=8,
                     d_ff=2816, head_dim=128, batch=8),
        "470m": dict(d_model=1536, n_layers=16, n_heads=12, n_kv_heads=12,
                     d_ff=4224, head_dim=128, batch=4),
    }[args.lm_size]
    if on_tpu:
        cfg = TransformerConfig(
            vocab_size=32_000, max_seq_len=seq,
            **{k: v for k, v in sizes.items() if k != "batch"},
            dtype=jnp.bfloat16, attention=args.attention,
            remat=not args.no_remat,
            remat_policy=args.remat_policy,
            save_attn_residuals=not args.no_save_attn,
            flash_block_q=args.flash_block_q,
            flash_block_k=args.flash_block_k,
            flash_block_diag=args.flash_block_diag,
            moe_experts=args.moe_experts,
            moe_group_size=args.moe_group_size,
            moe_impl=args.moe_impl,
            ce_dtype=args.ce_dtype,
            ce_chunk=args.ce_chunk,
        )
        batch = args.batch or sizes["batch"] * n_chips
    else:  # tiny hermetic config for --fake-devices runs
        cfg = TransformerConfig(
            vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
            d_ff=128, head_dim=16, max_seq_len=seq, dtype=jnp.float32,
            attention="dot",  # flash falls back off-TPU anyway
            remat=not args.no_remat,
            remat_policy=args.remat_policy,
            save_attn_residuals=not args.no_save_attn,
            moe_experts=args.moe_experts,
            moe_group_size=args.moe_group_size,
            moe_impl=args.moe_impl,
            ce_dtype=args.ce_dtype,
            ce_chunk=args.ce_chunk,
        )
        batch = args.batch or 4 * n_chips
    print(
        f"bench: lm train step ({cfg.attention} attention), "
        f"{n_chips}x{devices[0].device_kind}, batch {batch} x seq {seq}",
        file=sys.stderr,
    )
    peak = peak_flops(devices[0])
    mesh = MeshSpec(data=n_chips).build(devices)
    init_fn, loss_fn = lm_task(cfg, mesh=mesh)
    # adafactor: factored second moment — the optimizer read/write
    # traffic (profiled at ~23 ms/step of the MoE step's 422 ms) drops
    # to O(rows + cols) per matrix.  Trainer takes any optax tx; this
    # flag just makes the trade measurable in-bench.
    tx = (optax.adafactor(1e-3) if args.optimizer == "adafactor"
          else optax.adamw(1e-3))
    trainer = Trainer(
        init_fn=init_fn, loss_fn=loss_fn, tx=tx, mesh=mesh,
        metrics=MetricsLogger(stream=sys.stderr),
        flops_per_example=cfg.flops_per_token() * seq,
        peak_flops_per_chip=peak,
    )
    state = trainer.create_state()
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, cfg.vocab_size, size=(batch, seq)).astype(
        np.int32)
    dev_batch = trainer.shard_batch({"tokens": tokens})
    step_s = measure_fit(trainer, state, dev_batch, args.warmup,
                         args.steps, steps_per_call=args.steps_per_call)
    print(f"steady state: {step_s*1e3:.2f} ms/step", file=sys.stderr)
    tokens_per_sec = batch * seq / step_s
    flops_per_step = 3 * cfg.flops_per_token() * batch * seq
    # No peak off-TPU (runtime.metrics.peak_flops): no utilization either.
    achieved_mfu = (mfu(flops_per_step, step_s, n_chips, peak)
                    if peak else None)
    return {
        "metric": "lm_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec / n_chips, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": achieved_mfu and round(achieved_mfu / 0.50, 4),
        "detail": {
            "tokens_per_sec": round(tokens_per_sec, 2),
            "step_time_ms": round(step_s * 1e3, 2),
            "global_batch": batch,
            "seq_len": seq,
            "attention": cfg.attention,
            "n_chips": n_chips,
            "mfu": achieved_mfu and round(achieved_mfu, 4),
            "device": devices[0].device_kind,
            "lm_size": args.lm_size,
            "optimizer": args.optimizer,
            **({"moe_experts": cfg.moe_experts,
                "moe_top_k": cfg.moe_top_k,
                "moe_group_size": cfg.resolved_moe_group_size(),
                "moe_impl": cfg.moe_impl}
               if cfg.moe_experts else {}),
        },
    }


def bench_serving(args, devices, n_chips, on_tpu):
    """Serving plane: predict p50/p99 latency + micro-batcher throughput.

    The reference shipped only a correctness golden for its serving path
    (components/k8s-model-server/images/test-worker/result.txt) — no
    latency numbers.  This measures the first-party server end to end:
    export -> versioned load -> jitted predict, single-request latency,
    and coalesced throughput through the pipelined MicroBatcher.

    The wire contract is uint8 images (the reference's clients sent raw
    image bytes, inception-client/label.py) — a quarter of float32's
    transfer bytes.  The environment's host<->device link is profiled
    first (sustained upload MB/s with a consumer forcing real arrival,
    plus the resident-input launch round trip) because serving
    throughput here is min(wire ceiling, device capacity): where the
    link is slow it bounds the big-image batcher numbers, so a
    small-image scenario is measured as well to show the batcher's own
    capacity when the wire is not the wall.
    """
    import tempfile
    import threading

    import jax
    import numpy as np

    from kubeflow_tpu.models.resnet import ResNetConfig
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.model_server import MicroBatcher, ModelServer

    family = "resnet50" if on_tpu else "resnet18"
    size = 224 if on_tpu else 64
    small_family, small_size = "resnet18", 64
    print(f"bench: serving predict, {family} @ {size}px uint8 wire, "
          f"{devices[0].device_kind}", file=sys.stderr)

    def percentiles(times):
        times = sorted(times)

        def pick(q):
            return times[max(0, math.ceil(len(times) * q) - 1)] * 1e3

        return times[len(times) // 2] * 1e3, pick(0.9), pick(0.99)

    def export_model(tmp, fam, px):
        model = ResNetConfig(name=fam).build()
        variables = model.init(
            jax.random.key(0), np.zeros((1, px, px, 3), np.float32),
            train=False)
        base = f"{tmp}/{fam}-{px}"
        export(base, 1, variables,
               loader="kubeflow_tpu.serving.loaders:classifier",
               config={"family": fam, "num_classes": 1000,
                       "num_filters": 64})
        return base

    def batcher_run(server, fam, image, n_clients, per_client,
                    max_batch=16, in_flight=4, batch_timeout_s=0.005):
        sizes = [s for s in (1, 2, 4, 8, 16, 32, 64) if s <= max_batch]
        batcher = MicroBatcher(
            lambda inputs: server.predict(fam, inputs),
            max_batch_size=max_batch, batch_timeout_s=batch_timeout_s,
            allowed_batch_sizes=sizes,
            in_flight=in_flight, name=fam,
        )
        req_s, stats, failures = closed_loop_clients(
            batcher, lambda: {"image": image}, n_clients, per_client)
        batcher.close()
        if failures:
            print(f"batcher_run: {failures} failed requests",
                  file=sys.stderr)
        return req_s, stats

    rng = np.random.RandomState(0)
    with tempfile.TemporaryDirectory() as tmp:
        base = export_model(tmp, family, size)
        server = ModelServer()
        server.add_model(family, base)

        image = rng.randint(0, 256, (1, size, size, 3)).astype(np.uint8)
        payload_mb = image.nbytes / 1e6
        reps = 100 if on_tpu else 10
        # Pre-compile each padded size, through 64: the capacity run
        # batches up to 64 — on an RTT- or bandwidth-bound link, rows
        # per round trip is the one lever the server controls.
        warm_sizes = (1, 2, 4, 8, 16, 32, 64) if on_tpu else (1, 2, 4)
        for b in warm_sizes:
            server.predict(family,
                           {"image": np.repeat(image, b, axis=0)})

        # --- link profile: launch RTT (resident input) and sustained
        # upload bandwidth (fresh input, consumer forces real arrival;
        # a bare device_put is lazily acked here and measures nothing).
        # The consumer is a trivial jitted reduce, NOT the model, so the
        # probe isolates the transfer: subtracting a model forward would
        # fold fwd(16)-fwd(1) compute into "upload" on fast links.
        # Acks MATERIALIZE (np.asarray) rather than block_until_ready:
        # a value on the host cannot arrive before the device produced
        # it, and these probes feed the wire-vs-server attribution — a
        # fooled probe here misdirects the whole serving analysis.
        import jax.numpy as jnp

        consume = jax.jit(lambda x: jnp.sum(x, dtype=jnp.int32))
        big = np.repeat(image, 16, axis=0)
        dev_big = jax.device_put(big)
        np.asarray(consume(dev_big))  # compile
        rtts = []
        for _ in range(5):
            t0 = time.perf_counter()
            np.asarray(consume(dev_big))
            rtts.append(time.perf_counter() - t0)
        launch_rtt_s = sorted(rtts)[len(rtts) // 2]
        ups = []
        for _ in range(3):
            fresh = big ^ rng.randint(
                0, 256, big.shape).astype(np.uint8)  # defeat dedup
            t0 = time.perf_counter()
            np.asarray(consume(fresh))
            ups.append(time.perf_counter() - t0)
        upload_s = max(1e-9, sorted(ups)[len(ups) // 2] - launch_rtt_s)
        upload_mb_s = big.nbytes / 1e6 / upload_s
        wire_ceiling = upload_mb_s / payload_mb
        dev_image = jax.device_put(image)
        np.asarray(server.predict(family, {"image": dev_image})["scores"])

        # --- RPC parallelism: can concurrent predict round trips
        # overlap, or does the transport serialize them?  This decides
        # whether in_flight executors buy pipeline depth (they cannot
        # beat a serialized transport).
        def sync_rt():
            np.asarray(server.predict(family, {"image": dev_big})
                       ["scores"])

        sync_rt()
        t0 = time.perf_counter()
        sync_rt()
        one_rt_s = time.perf_counter() - t0
        n_par = 8
        par_threads = [threading.Thread(target=sync_rt)
                       for _ in range(n_par)]
        t0 = time.perf_counter()
        for t in par_threads:
            t.start()
        for t in par_threads:
            t.join()
        par_s = time.perf_counter() - t0
        rpc_parallelism = n_par * one_rt_s / max(par_s, 1e-9)

        # No device-side probe here: benchmark/lib/trace_reduce.py reads
        # a trace; on every CPU run this was None already.
        device_ms_per_batch = None

        # --- single-request sync latency (full round trip per call).
        lat = []
        for _ in range(reps):
            t0 = time.perf_counter()
            out = server.predict(family, {"image": image})
            np.asarray(out["scores"])  # block on the result
            lat.append(time.perf_counter() - t0)
        p50, p90, p99 = percentiles(lat)

        # --- sustained (pipelined) predict: dispatch without per-call
        # blocking, block once — the chip-side cost a co-located server
        # amortises to.
        t0 = time.perf_counter()
        outs = [server.predict(family, {"image": dev_image})["scores"]
                for _ in range(reps)]
        jax.block_until_ready(outs)
        sustained_ms = (time.perf_counter() - t0) / reps * 1e3

        # --- batcher, headline model: 16 closed-loop clients, then a
        # capacity run.  Capacity batches to 64 (not 16): on a
        # round-trip- or bandwidth-bound transport, rows per round trip
        # is the server's one lever.  The 50 ms accumulation window and
        # 4 executors make saturated dispatches go out FULL — with a
        # 5 ms window and 8 executors the mean dispatch carried ~17 of
        # 64 rows and the host-side padding to the compiled size was
        # transferred as dead bytes (~2x the wire for the same goodput,
        # measured 108.9 req/s vs 142.6 at max_batch=16).
        n_clients, per_client = (16, 16) if on_tpu else (4, 4)
        qps, stats = batcher_run(server, family, image,
                                 n_clients, per_client)
        cap_clients, cap_per = (256, 6) if on_tpu else (16, 2)
        cap_batch = 64 if on_tpu else 4
        cap_qps, cap_stats = batcher_run(
            server, family, image, cap_clients, cap_per,
            max_batch=cap_batch, in_flight=4,
            batch_timeout_s=0.05 if on_tpu else 0.005)

        # --- batcher, small-image scenario: the wire is no longer the
        # wall, so this shows the batching layer's own capacity.  Batch
        # 64 amortises the per-execution dispatch round trip (the
        # binding constraint once payloads are small) over 4x the rows.
        small = {}
        if on_tpu:
            sbase = export_model(tmp, small_family, small_size)
            server.add_model("small", sbase)
            simage = rng.randint(
                0, 256, (1, small_size, small_size, 3)).astype(np.uint8)
            for b in (1, 2, 4, 8, 16, 32, 64):
                server.predict("small",
                               {"image": np.repeat(simage, b, axis=0)})
            # Small payloads are round-trip-bound, not bandwidth-bound:
            # partial batches in flight overlap more round trips, so the
            # SHORT window wins here (the big-image capacity run wants
            # the opposite — full batches per round trip).
            sqps, sstats = batcher_run(server, "small", simage, 256, 8,
                                       max_batch=64, in_flight=4)
            small = {
                "model": small_family,
                "image_size": small_size,
                "payload_kb": round(simage.nbytes / 1e3, 1),
                "requests_per_sec": round(sqps, 1),
                "clients": 256,
                "max_batch_size": 64,
                "mean_batch_size": sstats["mean_batch_size"],
                "cycle_profile_ms": sstats["cycle_profile_ms"],
                "max_pipeline_depth": sstats["max_pipeline_depth"],
            }
    print(f"serving: sync p50 {p50:.1f} ms (p90 {p90:.1f} p99 {p99:.1f})"
          f", sustained {sustained_ms:.2f} ms/req, link "
          f"{upload_mb_s:.1f} MB/s up / rtt {launch_rtt_s*1e3:.0f} ms, "
          f"batched {qps:.1f} req/s @{n_clients} (mean batch "
          f"{stats['mean_batch_size']}), capacity {cap_qps:.1f} req/s "
          f"@{cap_clients} (mean batch {cap_stats['mean_batch_size']})"
          + (f", small-image {small['requests_per_sec']} req/s"
             if small else ""),
          file=sys.stderr)
    return {
        "metric": "serving_predict_sustained_ms",
        "value": round(sustained_ms, 2),
        "unit": "ms/request (pipelined batch-1)",
        "detail": {
            "model": family,
            "image_size": size,
            "wire_dtype": "uint8",
            "payload_kb": round(payload_mb * 1e3, 1),
            "sustained_ms_per_request": round(sustained_ms, 2),
            "sync_predict_p50_ms": round(p50, 2),
            "sync_predict_p90_ms": round(p90, 2),
            "sync_predict_p99_ms": round(p99, 2),
            "sync_includes_dispatch_round_trip": True,
            "link_upload_mb_s": round(upload_mb_s, 1),
            "link_launch_rtt_ms": round(launch_rtt_s * 1e3, 1),
            "wire_ceiling_req_s": round(wire_ceiling, 1),
            "link_probe_ack": "np.asarray (materialized)",
            "sync_batch16_round_trip_ms": round(one_rt_s * 1e3, 1),
            "link_rpc_parallelism": round(rpc_parallelism, 1),
            **({"device_ms_per_batch16":
                round(device_ms_per_batch, 2),
                "device_ceiling_req_s":
                round(16e3 / device_ms_per_batch, 1)}
               if device_ms_per_batch else {}),
            "batcher_requests_per_sec": round(qps, 1),
            "batcher_clients": n_clients,
            "batcher_mean_batch_size": stats["mean_batch_size"],
            "batcher_batch_size_hist": stats["batch_size_hist"],
            "batcher_cycle_profile_ms": stats["cycle_profile_ms"],
            "batcher_capacity_requests_per_sec": round(cap_qps, 1),
            "batcher_capacity_clients": cap_clients,
            "batcher_capacity_max_batch": cap_batch,
            "batcher_capacity_mean_batch_size":
                cap_stats["mean_batch_size"],
            "batcher_capacity_cycle_profile_ms":
                cap_stats["cycle_profile_ms"],
            "batcher_capacity_pipeline_depth":
                cap_stats["max_pipeline_depth"],
            # The judged ratios, precomputed: capacity against the
            # measured wire ceiling (payload_kb over the link's honest
            # upload bandwidth) and against the XProf device ceiling —
            # which wall the serving stack is actually at.
            "capacity_vs_wire_ceiling": round(
                cap_qps / wire_ceiling, 3) if wire_ceiling else None,
            **({"capacity_vs_device_ceiling": round(
                cap_qps * device_ms_per_batch / 16e3, 5)}
               if device_ms_per_batch else {}),
            "batcher_small_image": small,
            "device": devices[0].device_kind,
        },
    }


def bench_lm_decode(args, devices, n_chips, on_tpu):
    """LM serving decode: batch-1 latency + batched throughput.

    Exercises the exact deployed path — export -> versioned load ->
    loaders:lm_generate (KV-cache decode, one jitted program for
    prefill + all steps).  The reference had no LM serving at all; its
    flagship golden was Inception (testing/test_tf_serving.py).  The
    whole generation is ONE device program: the dispatch round trip
    amortizes over every generated token instead of being paid per
    token.
    """
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.model_server import ModelServer

    if on_tpu:
        overrides = {
            "vocab_size": 32_000, "d_model": 1024, "n_layers": 12,
            "n_heads": 8, "n_kv_heads": 8, "d_ff": 2816, "head_dim": 128,
            "max_seq_len": 2048, "dtype": "bfloat16",
        }
        prompt_len, new_tokens, batch = 128, 128, args.batch or 8
    else:
        overrides = {
            "vocab_size": 256, "d_model": 64, "n_layers": 2, "n_heads": 4,
            "n_kv_heads": 4, "d_ff": 128, "head_dim": 16,
            "max_seq_len": 128, "dtype": "float32",
        }
        prompt_len, new_tokens, batch = 16, 16, args.batch or 4
    if args.decode_prompt_len:
        # Long-context serving sweep knob: a long prompt's prefill runs
        # the flash kernel (O(t) memory) when the model is
        # flash-configured — the dot path's [b, h, t, t] scores would be
        # the limiter (models/generate.py).
        prompt_len = args.decode_prompt_len
        overrides["max_seq_len"] = max(
            overrides["max_seq_len"], prompt_len + new_tokens)
        overrides["attention"] = "flash"
        if args.kv_cache == "int8":
            # The generate() gate keeps flash OFF for quantized caches
            # (serving goldens pin the dot path's cache rounding) — say
            # so, or a long-context sweep gets attributed to the wrong
            # prefill kernel.
            print("lm-decode: NOTE --kv-cache int8 disables flash "
                  "prefill; this run measures the dot-path prefill",
                  file=sys.stderr)
    print(f"bench: lm decode, d_model={overrides['d_model']} "
          f"L{overrides['n_layers']}, prompt {prompt_len} + {new_tokens} "
          f"new, {devices[0].device_kind}", file=sys.stderr)
    cfg = _model_config(overrides)
    model = Transformer(cfg)
    rng = np.random.RandomState(0)
    init_tokens = jnp.zeros((1, prompt_len), jnp.int32)
    variables = model.init(jax.random.key(0), init_tokens)
    with tempfile.TemporaryDirectory() as tmp:
        config = {"model": overrides, "max_new_tokens": new_tokens,
                  "temperature": 0.0}
        if args.quantize:
            config["quantize"] = args.quantize
        if args.kv_cache:
            config["kv_cache"] = args.kv_cache
        export(f"{tmp}/lm", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config=config)
        server = ModelServer()
        server.add_model("lm", f"{tmp}/lm")

        def decode(b):
            prompt = rng.randint(1, cfg.vocab_size, size=(b, prompt_len))
            out = server.predict(
                "lm", {"tokens": prompt.astype(np.int32)})
            # Materialize to host rather than block_until_ready: the
            # output is a few KB of int32, and np.asarray cannot return
            # before the device executed — the timing is structurally
            # un-foolable instead of assumed correct.
            np.asarray(out["tokens"])

        # Best median of two INTERLEAVED windows: a single median-of-5
        # window can be poisoned by one multi-second host stall
        # spanning >=3 reps.  Interleaving batch-1/batched windows
        # puts real wall-time between same-shape windows, so one
        # freeze cannot silently poison both; the faster median is the
        # throughput-capability estimator, and the per-window medians
        # ship in the record (window_spread_suspect stamps a >2x
        # spread the way timing_suspect stamps the physical floor).
        reps = 5 if on_tpu else 2

        def timed_window(b):
            lat = []
            for _ in range(reps):
                t0 = time.perf_counter()
                decode(b)
                lat.append(time.perf_counter() - t0)
            return sorted(lat)[len(lat) // 2]

        decode(1)      # compile batch-1
        decode(batch)  # compile batched
        m1, mb = [], []
        for _ in range(2 if on_tpu else 1):
            m1.append(timed_window(1))
            mb.append(timed_window(batch))
        lat1_s, latb_s = min(m1), min(mb)
        window_spread = (max(m1) > 2 * min(m1)
                         or max(mb) > 2 * min(mb))
        if window_spread:
            print(f"lm decode: window medians spread >2x "
                  f"(b1 {[round(x*1e3) for x in m1]} ms, "
                  f"b{batch} {[round(x*1e3) for x in mb]} ms) — "
                  f"a stall in the slow window", file=sys.stderr)

        # Concurrent clients through the shape-grouped MicroBatcher:
        # uniform-length batch-1 requests coalesce into the SAME batched
        # generate program measured above (allowed sizes reuse its
        # compile), so this measures the serving plane's coalescing, not
        # a new program.
        from kubeflow_tpu.serving.model_server import MicroBatcher

        n_clients, per_client = batch, 2 if on_tpu else 1

        def median_trials(make_batcher, make_inputs, label):
            """Median req/s over repeated closed-loop windows, with the
            MEDIAN trial's batcher stats (a single short window
            spreads widely; pairing the median throughput
            with another trial's mean batch size would misdescribe the
            reported measurement).  Failures accumulate across trials.
            """
            trials, failures = [], 0
            for _ in range(3 if on_tpu else 1):
                batcher = make_batcher()
                req_s, stats, fails = closed_loop_clients(
                    batcher, make_inputs, n_clients, per_client)
                batcher.close()
                failures += fails
                trials.append((req_s, stats))
            trials.sort(key=lambda t: t[0])
            req_s, stats = trials[len(trials) // 2]
            if failures:
                print(f"{label}: {failures} failed requests",
                      file=sys.stderr)
            return req_s, stats

        batcher_req_s, mb_stats = median_trials(
            lambda: MicroBatcher(
                server.get("lm").predict, max_batch_size=batch,
                batch_timeout_s=0.02, allowed_batch_sizes=[1, batch],
                in_flight=2, name="lm",
            ),
            lambda: {"tokens": rng.randint(
                1, cfg.vocab_size, size=(1, prompt_len)
            ).astype(np.int32)},
            "lm batcher")

        # MIXED-length clients through the BucketedLMBatcher: prompts
        # of three different lengths share ONE queue
        # and pad at dispatch to the batch's largest bucket (promotion),
        # so they share batched generate programs instead of degrading
        # to batch-1 per unique shape (round 3) or splitting per bucket
        # (the submit-time-padding design: measured 4.8 req/s at mean
        # batch 2.67 vs uniform 25.4).  Promoted rows pay the batch
        # bucket's KV span per decode step (see BucketedLMBatcher), a
        # cost this round-trip-dominated workload doesn't feel.
        # Target: within ~2x of the uniform-length number above.
        import random as _random

        from kubeflow_tpu.serving.model_server import BucketedLMBatcher

        half = max(1, prompt_len // 2)
        lengths = [half, max(1, (3 * prompt_len) // 4), prompt_len]

        def make_bucketed():
            return BucketedLMBatcher(
                server.get("lm").predict,
                buckets=[half, prompt_len],
                max_batch_size=batch, batch_timeout_s=0.02,
                allowed_batch_sizes=[1, batch], in_flight=2,
                name="lm-bucketed",
            )

        pick = _random.Random(0)

        def mixed_inputs():
            return {"tokens": rng.randint(
                1, cfg.vocab_size, size=(1, pick.choice(lengths))
            ).astype(np.int32)}

        # Deterministic warm-up: compile EVERY (bucket, allowed size)
        # generate program the timed run can hit.  Dispatch-time
        # promotion makes the bucket a batch-composition property (a
        # lone half-length straggler dispatches at the half bucket;
        # mixed batches promote to the full one), so a client-driven
        # warm pass cannot be trusted to cover the combinations —
        # any it misses lands a multi-second XLA compile inside the
        # timed window.  Jit caches are global, so the timed batcher
        # starts warm with clean stats.
        predict_fn = server.get("lm").predict
        for bucket in (half, prompt_len):
            for size in (1, batch):
                warm_tokens = rng.randint(
                    1, cfg.vocab_size, size=(size, bucket)
                ).astype(np.int32)
                out = predict_fn({
                    "tokens": warm_tokens,
                    "prompt_len": np.full((size,), bucket, np.int32),
                })
                jax.block_until_ready(out["tokens"])

        # Same median-of-trials treatment: single windows measured
        # anywhere from 15 to 33 req/s across runs before this.
        mixed_req_s, bmb_stats = median_trials(
            make_bucketed, mixed_inputs, "lm bucketed batcher")

        # Promotion-cost probe (device-side): the SAME prompts decoded
        # at their natural bucket vs left-padded to an 8x bucket — the
        # per-step KV span a promoted row pays, and the measured
        # justification for BucketedLMBatcher's max_promotion_factor
        # bound (a round-trip-dominated closed loop can't feel this
        # cost; the device does, every decode step).
        promotion = {}
        wide_bucket = 8 * prompt_len
        if on_tpu and overrides["max_seq_len"] >= wide_bucket + new_tokens:
            nat_prompts = rng.randint(
                1, cfg.vocab_size, size=(batch, prompt_len)
            ).astype(np.int32)
            padded = np.concatenate(
                [np.zeros((batch, wide_bucket - prompt_len), np.int32),
                 nat_prompts], axis=1)
            plens = np.full((batch,), prompt_len, np.int32)

            def timed_decode(tokens):
                inp = {"tokens": tokens, "prompt_len": plens}
                np.asarray(predict_fn(inp)["tokens"])  # compile/warm
                ts = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    np.asarray(predict_fn(inp)["tokens"])
                    ts.append(time.perf_counter() - t0)
                return sorted(ts)[1]

            t_nat = timed_decode(nat_prompts)
            t_pad = timed_decode(padded)
            promotion = {
                "natural_bucket": prompt_len,
                "promoted_bucket": wide_bucket,
                "natural_ms": round(t_nat * 1e3, 1),
                "promoted_ms": round(t_pad * 1e3, 1),
                "promotion_step_cost_ratio": round(t_pad / t_nat, 2),
            }
            print(f"promotion cost: bucket {prompt_len} {t_nat*1e3:.0f} "
                  f"ms vs promoted {wide_bucket} {t_pad*1e3:.0f} ms "
                  f"({t_pad/t_nat:.2f}x)", file=sys.stderr)
    tok_s_b1 = new_tokens / lat1_s
    tok_s = batch * new_tokens / latb_s
    # Belt over the asarray suspenders: decode steps are SEQUENTIAL
    # (batch rows run in parallel, steps don't), and no TPU device
    # step completes in under 0.01 ms, so a median latency below
    # new_tokens * 0.01 ms is physically impossible at any batch size
    # — stamp the record as suspect instead of shipping an absurd
    # number silently.  TPU-only: the tiny CPU smoke config can
    # legitimately decode faster than a device-step floor derived
    # from TPU dispatch.  A conservative static bound; the structural
    # defense is the host materialization above.
    timing_suspect = on_tpu and (lat1_s < new_tokens * 1e-5
                                 or latb_s < new_tokens * 1e-5)
    print(f"lm decode: batch-1 {lat1_s*1e3:.1f} ms ({tok_s_b1:.1f} tok/s,"
          f" {lat1_s/new_tokens*1e3:.2f} ms/tok), batch-{batch} "
          f"{tok_s:.1f} tok/s", file=sys.stderr)
    return {
        "metric": "lm_decode_tokens_per_sec",
        "value": round(tok_s, 1),
        "unit": f"tokens/sec (batch {batch}, KV-cache decode)",
        "detail": {
            "batch1_latency_ms": round(lat1_s * 1e3, 1),
            "batch1_ms_per_token": round(lat1_s / new_tokens * 1e3, 2),
            "batch1_tokens_per_sec": round(tok_s_b1, 1),
            "batched_tokens_per_sec": round(tok_s, 1),
            "batch": batch,
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "d_model": overrides["d_model"],
            "n_layers": overrides["n_layers"],
            "device": devices[0].device_kind,
            "batcher_requests_per_sec": round(batcher_req_s, 1),
            "batcher_clients": n_clients,
            "batcher_mean_batch_size": mb_stats["mean_batch_size"],
            "batcher_tokens_per_sec": round(
                batcher_req_s * new_tokens, 1),
            "batcher_mixed_requests_per_sec": round(mixed_req_s, 1),
            "batcher_mixed_mean_batch_size":
                bmb_stats["mean_batch_size"],
            "batcher_mixed_lengths": lengths,
            **({"promotion_cost": promotion} if promotion else {}),
            "window_medians_ms": {
                "batch1": [round(x * 1e3, 1) for x in m1],
                "batched": [round(x * 1e3, 1) for x in mb],
            },
            **({"window_spread_suspect": True} if window_spread
               else {}),
            **({"quantize": args.quantize} if args.quantize else {}),
            **({"kv_cache": args.kv_cache} if args.kv_cache else {}),
            **({"timing_suspect": True} if timing_suspect else {}),
        },
    }


def _pct_ms(values, q):
    """q-quantile of a list of seconds, in ms (0.0 when empty)."""
    if not values:
        return 0.0
    values = sorted(values)
    return round(values[min(len(values) - 1,
                            int(len(values) * q))] * 1e3, 3)


def _bench_shared_prefix(spec, rng, cfg, on_tpu, DecodeEngine):
    """Shared-prefix workload: N clients, one common 64-token system
    prompt plus a unique per-client suffix, measured with the prefix
    cache ON and OFF on otherwise identical engines.  Reports TTFT
    p50/p99 for both sides, the ON/OFF speedup (acceptance: >= 1.3x at
    p50), the cached-token ratio, and the inter-token-gap profile under
    concurrent admission (chunked prefill's no-stall guarantee)."""
    import threading

    import numpy as np

    if on_tpu:
        shared_len, suffix_len, n_clients = 64, 16, 32
        prefill, chunk, block, probe_new = 256, 32, 16, 8
        workers = 4
    else:
        shared_len, suffix_len, n_clients = 64, 8, 24
        prefill, chunk, block, probe_new = 80, 8, 16, 4
        workers = 2
    shared = rng.randint(1, cfg.vocab_size,
                         size=(shared_len,)).astype(np.int32)
    suffixes = [rng.randint(1, cfg.vocab_size,
                            size=(suffix_len,)).astype(np.int32)
                for _ in range(n_clients)]
    warm = rng.randint(1, cfg.vocab_size,
                       size=(1, shared_len + suffix_len)).astype(np.int32)

    def run(caching):
        engine = DecodeEngine(
            spec["cfg"], spec["params"], spec["decode"], slots=4,
            prefill_len=prefill, prefill_chunk_tokens=chunk,
            kv_block_tokens=block, prefix_caching=caching,
            name=f"bench-prefix-{int(caching)}")
        try:
            # Compile all three programs on an UNRELATED prompt so the
            # first shared-prefix client is the real cache miss.
            engine.submit({"tokens": warm, "max_new_tokens": 2})
            ttfts = []
            t_lock = threading.Lock()
            sem = threading.Semaphore(workers)

            def client(suffix):
                prompt = np.concatenate([shared, suffix])[None]
                with sem:
                    out = engine.submit({
                        "tokens": prompt, "max_new_tokens": probe_new,
                        "return_timing": True})
                with t_lock:
                    ttfts.append(out["ttft_s"])

            threads = [threading.Thread(target=client, args=(s,))
                       for s in suffixes]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return ttfts, engine.stats()
        finally:
            engine.close()

    on_ttfts, on_stats = run(caching=True)
    off_ttfts, off_stats = run(caching=False)
    on_p50, off_p50 = _pct_ms(on_ttfts, 0.5), _pct_ms(off_ttfts, 0.5)
    speedup = off_p50 / on_p50 if on_p50 else 0.0
    print(f"shared-prefix: TTFT p50 cache ON {on_p50:.2f} ms vs OFF "
          f"{off_p50:.2f} ms ({speedup:.2f}x), cached-token ratio "
          f"{on_stats['cached_token_ratio']}, gap p99 ON "
          f"{on_stats['inter_token_gap_p99_ms']} ms", file=sys.stderr)
    return {
        "shared_prefix_tokens": shared_len,
        "suffix_tokens": suffix_len,
        "clients": n_clients,
        "prefill_chunk_tokens": chunk,
        "kv_block_tokens": block,
        "ttft_p50_ms_cache_on": on_p50,
        "ttft_p99_ms_cache_on": _pct_ms(on_ttfts, 0.99),
        "ttft_p50_ms_cache_off": off_p50,
        "ttft_p99_ms_cache_off": _pct_ms(off_ttfts, 0.99),
        "ttft_speedup_p50": round(speedup, 3),
        "cached_token_ratio": on_stats["cached_token_ratio"],
        "prefix_hits": on_stats["prefix_hits"],
        "prefix_misses": on_stats["prefix_misses"],
        "inter_token_gap_p50_ms_cache_on":
            on_stats["inter_token_gap_p50_ms"],
        "inter_token_gap_p99_ms_cache_on":
            on_stats["inter_token_gap_p99_ms"],
        "inter_token_gap_max_ms_cache_on":
            on_stats["inter_token_gap_max_ms"],
        "inter_token_gap_p99_ms_cache_off":
            off_stats["inter_token_gap_p99_ms"],
        "inter_token_gap_max_ms_cache_off":
            off_stats["inter_token_gap_max_ms"],
        "prefill_chunks_cache_off": off_stats["prefill_chunks"],
    }


def _bench_paged_kv(spec, rng, cfg, on_tpu, DecodeEngine):
    """Paged-KV capacity probe: how many mixed-length requests fit the
    SAME device KV token budget once capacity is bounded by tokens
    resident instead of slots x max_len.

    Two engines over one fixed block budget (the pool a slot-reserved
    cache of ``baseline_slots`` worst-case rows would occupy):

      * baseline — ``slots = budget // blocks_per_max_len``: admission
        is bounded by slot count at worst-case parity, which IS the
        old slot-reserved capacity model (every admission costs a full
        max_len row no matter how short the request);
      * paged — many slots, same pool: each admission reserves only
        ceil((prompt + budget) / block) pages, so short requests
        co-reside where the baseline would make them queue.

    One open-loop mixed-length workload (short/medium/long prompts
    interleaved, seeded arrivals) runs on both; a sampler thread
    records the PEAK concurrent resident requests and the window
    records delivered tok/s.  Windows interleave with alternating
    order and the max window is the capability estimate, as
    everywhere else in this bench.  Acceptance: paged holds >= 1.5x
    the baseline's peak concurrency at the same token budget, with
    delivered throughput no worse."""
    import threading

    import numpy as np

    if on_tpu:
        # ISSUE geometry: lengths 64/256/1024-class against a
        # max_len-1024 config (prompt capped at the prefill width).
        lens = [64, 256, 832]
        prefill, probe_new, block = 896, 128, 16
        n_requests, spread_s, baseline_slots, windows = 48, 0.05, 4, 2
    else:
        # Same shape scaled to the hermetic CPU model (max_seq_len
        # 128): lengths 8/32/96 against a max_len-128 config.
        lens = [8, 32, 96]
        prefill, probe_new, block = 96, 16, 16
        n_requests, spread_s, baseline_slots, windows = 48, 0.002, 4, 3
    max_len = prefill + probe_new
    table_blocks = -(-max_len // block)
    budget_blocks = baseline_slots * table_blocks
    paged_slots = 4 * baseline_slots
    reqs = [
        (rng.randint(1, cfg.vocab_size,
                     size=(lens[i % len(lens)],)).astype(np.int32),
         rng.uniform(0.0, spread_s))
        for i in range(n_requests)
    ]

    def make_engine(slots, label):
        engine = DecodeEngine(
            spec["cfg"], spec["params"], spec["decode"], slots=slots,
            prefill_len=prefill, max_len=max_len,
            kv_block_tokens=block, kv_pool_blocks=budget_blocks,
            prefix_caching=False, name=f"bench-paged-{label}")
        engine.submit({"tokens": reqs[0][0][:4],
                       "max_new_tokens": 2})  # warm both programs
        return engine

    def window(engine):
        stop = threading.Event()
        # Peak CONCURRENT RESIDENT requests = peak active slots
        # (sequences simultaneously holding KV — the capacity number
        # the pool bounds).  in_flight_requests would overcount here:
        # deterministic retirement frees a slot at dispatch while the
        # request stays in flight until its lagged delivery.
        peak = {"resident": 0, "kv_util": 0.0}

        def sampler():
            while not stop.is_set():
                st = engine.stats()
                peak["resident"] = max(peak["resident"],
                                       st["active_slots"])
                peak["kv_util"] = max(st["kv_utilization"],
                                      peak["kv_util"])
                time.sleep(0.002)

        failures = []

        def client(prompt, delay):
            time.sleep(delay)
            try:
                engine.submit({"tokens": prompt,
                               "max_new_tokens": probe_new})
            except Exception as exc:  # noqa: BLE001 — recorded
                failures.append(exc)

        sam = threading.Thread(target=sampler, daemon=True)
        sam.start()
        threads = [threading.Thread(target=client, args=r)
                   for r in reqs]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stop.set()
        sam.join(timeout=5)
        ok = n_requests - len(failures)
        return {
            "peak_in_flight": peak["resident"],
            "peak_kv_utilization": round(peak["kv_util"], 4),
            "tokens_per_sec": round(ok * probe_new / wall, 1),
            "failed_requests": len(failures),
        }

    base_engine = make_engine(baseline_slots, "slotres")
    paged_engine = make_engine(paged_slots, "paged")
    base_ws, paged_ws = [], []
    try:
        for w in range(windows):
            if w % 2 == 0:
                base_ws.append(window(base_engine))
                paged_ws.append(window(paged_engine))
            else:
                paged_ws.append(window(paged_engine))
                base_ws.append(window(base_engine))
    finally:
        base_engine.close()
        paged_engine.close()

    def best(ws):
        out = max(ws, key=lambda w: w["tokens_per_sec"])
        return {**out,
                "peak_in_flight": max(w["peak_in_flight"] for w in ws),
                "failed_requests": sum(w["failed_requests"]
                                       for w in ws)}

    base, paged = best(base_ws), best(paged_ws)
    conc = (paged["peak_in_flight"] / base["peak_in_flight"]
            if base["peak_in_flight"] else 0.0)
    print(f"paged-kv: peak resident {paged['peak_in_flight']} vs "
          f"slot-reserved {base['peak_in_flight']} ({conc:.2f}x) at "
          f"{budget_blocks} blocks; delivered "
          f"{paged['tokens_per_sec']} vs {base['tokens_per_sec']} "
          "tok/s", file=sys.stderr)
    return {
        "kv_pool_blocks": budget_blocks,
        "kv_block_tokens": block,
        "token_budget": budget_blocks * block,
        "max_len": max_len,
        "prompt_lens": lens,
        "probe_new_tokens": probe_new,
        "requests": n_requests,
        "baseline_slots": baseline_slots,
        "paged_slots": paged_slots,
        "slot_reserved": base,
        "paged": paged,
        "concurrency_ratio": round(conc, 3),
        "tokens_per_sec_ratio": round(
            paged["tokens_per_sec"] / base["tokens_per_sec"], 3)
        if base["tokens_per_sec"] else 0.0,
        # On the CPU smoke box a decode step's cost is ~linear in
        # batch width (compute-bound), so the extra co-residency buys
        # concurrency but not throughput; whether the same co-residency
        # multiplies delivered tok/s on the chip is not measured.
        **({} if on_tpu else {"cpu_compute_bound_note": True}),
    }


def _bench_kv_spill(spec, rng, cfg, on_tpu, DecodeEngine):
    """Hierarchical-KV probe (§5.10): what does the host spill tier
    BUY (tokens addressable) and what does it COST (delivered tok/s,
    resumed TTFT)?

    Two engines over the same TIGHT device pool run an identical
    multi-turn workload — every session parks its KV after turn 1
    (``park_kv``), then returns for turn 2 with its full context:

      * spill OFF — the parked mass exceeds the pool, so cold records
        are DESTROY-evicted and every turn 2 recomputes its prefill
        from scratch (the pre-§5.10 behavior);
      * spill ON — host_spill_blocks = 4x the device pool (tokens
        addressable = 5x HBM), cold records evacuate to host RAM and
        turn 2 re-imports them through the kv_import program.

    Recorded: delivered tok/s both sides and their ratio (the <10%
    spill-machinery cost bound is a METAL acceptance: there the
    prefill recompute spilling avoids is the quadratic FLOPs term, so
    re-import wins outright), the spill/shed/evict counter story (ON
    must shed nothing and destroy nothing), greedy token identity ON
    vs OFF, and resumed-vs-cold TTFT (submit of a parked session's
    full context against a never-seen context of the same length).
    The hermetic CPU box inverts the trade — prefill compute is
    nearly free while host copies and kv_import dispatches are real
    work — so both the recorded ratio and the TTFT gap UNDERSTATE
    metal; cpu_compute_bound_note marks the record."""
    import threading

    import numpy as np

    if on_tpu:
        # turn-2 prompt peaks at 448+64+4 = 516 <= prefill; two live
        # slots reserve 2*ceil(580/16) = 74 <= pool.
        lens = [256, 448]
        prefill, turn_new, block = 896, 64, 16
        pool_blocks, sessions, windows = 80, 8, 2
    else:
        # max_seq_len-128 hermetic model: turn-2 prompt peaks at
        # 56+16+4 = 76 <= prefill, max_len 104 <= 128; two live slots
        # reserve 2*ceil(92/16) = 12 of the 16-page pool, so parked
        # mass (~28 pages/window) always overflows to host but an
        # admission keeps a little cache headroom (never a shed).
        lens = [40, 56]
        prefill, turn_new, block = 80, 16, 16
        pool_blocks, sessions, windows = 16, 8, 3
    host_blocks = 4 * pool_blocks
    max_len = prefill + turn_new + 8
    extra_len = 4

    def make_engine(host, label):
        return DecodeEngine(
            spec["cfg"], spec["params"], spec["decode"], slots=2,
            prefill_len=prefill, max_len=max_len,
            kv_block_tokens=block, kv_pool_blocks=pool_blocks,
            host_spill_blocks=host, name=f"bench-spill-{label}")

    def window(engine, sess):
        """One multi-turn wave: turn 1 parked, then turn 2 resumes.
        Returns (delivered tok/s, turn-2 token streams)."""
        turn1_ctx = [None] * len(sess)

        def turn1(i):
            prompt, _ = sess[i]
            out = engine.submit({"tokens": prompt,
                                 "max_new_tokens": turn_new,
                                 "park_kv": True})
            turn1_ctx[i] = list(out["tokens"][0])

        def run_all(fn):
            threads = [threading.Thread(target=fn, args=(i,))
                       for i in range(len(sess))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        turn2_out = [None] * len(sess)

        def turn2(i):
            _, extra = sess[i]
            out = engine.submit(
                {"tokens": np.asarray(turn1_ctx[i] + extra, np.int32),
                 "max_new_tokens": turn_new})
            turn2_out[i] = list(out["tokens"][0])

        t0 = time.perf_counter()
        run_all(turn1)
        run_all(turn2)
        wall = time.perf_counter() - t0
        delivered = 2 * turn_new * len(sess)
        return round(delivered / wall, 1), turn2_out

    spill_eng = make_engine(host_blocks, "on")
    base_eng = make_engine(0, "off")
    for eng in (spill_eng, base_eng):  # warm prefill + step programs
        eng.submit({"tokens": np.arange(1, 5, dtype=np.int32),
                    "max_new_tokens": 2})
    # Warm the host-tier paths too (the park gather and the kv_import
    # program a re-admission scatters through) so window 0 measures
    # the machinery, not its compilation.
    warm = rng.randint(1, cfg.vocab_size,
                       size=(lens[0],)).astype(np.int32)
    out = spill_eng.submit({"tokens": warm, "max_new_tokens": turn_new,
                            "park_kv": True})
    spill_eng.submit({"tokens": np.asarray(
        list(out["tokens"][0]) + [1] * extra_len, np.int32),
        "max_new_tokens": 2})
    on_rates, off_rates = [], []
    identical = True
    last_sess = None
    try:
        for w in range(windows):
            sess = [
                (rng.randint(1, cfg.vocab_size,
                             size=(lens[i % len(lens)],)
                             ).astype(np.int32),
                 rng.randint(1, cfg.vocab_size,
                             size=(extra_len,)).astype(np.int32)
                 .tolist())
                for i in range(sessions)
            ]
            last_sess = sess
            if w % 2 == 0:
                on_rate, on_toks = window(spill_eng, sess)
                off_rate, off_toks = window(base_eng, sess)
            else:
                off_rate, off_toks = window(base_eng, sess)
                on_rate, on_toks = window(spill_eng, sess)
            on_rates.append(on_rate)
            off_rates.append(off_rate)
            identical = identical and on_toks == off_toks

        on_stats = spill_eng.stats()
        off_stats = base_eng.stats()
        on_mgr = spill_eng._mgr.stats()
        off_mgr = base_eng._mgr.stats()

        # --- TTFT: a parked session's turn 2 (re-import) vs a cold
        # context of the SAME length on the warm baseline engine.
        ctx, extra = last_sess[0]
        out = spill_eng.submit({"tokens": ctx, "max_new_tokens":
                                turn_new, "park_kv": True})
        resumed_tokens = np.asarray(
            list(out["tokens"][0]) + extra, np.int32)
        out = spill_eng.submit({"tokens": resumed_tokens,
                                "max_new_tokens": 1,
                                "return_timing": True})
        resumed_ttft = out["ttft_s"]
        cold_tokens = rng.randint(
            1, cfg.vocab_size,
            size=resumed_tokens.shape).astype(np.int32)
        out = base_eng.submit({"tokens": cold_tokens,
                               "max_new_tokens": 1,
                               "return_timing": True})
        cold_ttft = out["ttft_s"]
    finally:
        spill_eng.close()
        base_eng.close()

    on_tok_s, off_tok_s = max(on_rates), max(off_rates)
    ratio = on_tok_s / off_tok_s if off_tok_s else 0.0
    print(f"kv-spill: {on_tok_s} tok/s with host tier vs {off_tok_s} "
          f"without ({ratio:.2f}x) at {pool_blocks}+{host_blocks} "
          f"blocks; resumed TTFT {resumed_ttft * 1e3:.1f} ms vs cold "
          f"{cold_ttft * 1e3:.1f} ms", file=sys.stderr)
    return {
        "kv_pool_blocks": pool_blocks,
        "host_spill_blocks": host_blocks,
        "kv_block_tokens": block,
        "tokens_addressable": on_stats["tokens_addressable"],
        # vs the device-only pool: the >= 5x HBM acceptance bound.
        "addressable_ratio": round(
            on_stats["tokens_addressable"]
            / (pool_blocks * block), 2),
        "sessions_per_window": sessions,
        "windows": windows,
        "spill_on_tokens_per_sec": on_tok_s,
        "spill_off_tokens_per_sec": off_tok_s,
        # Metal acceptance: >= 0.9 (the < 10% spill-machinery cost
        # bound); the CPU record understates — see the note below.
        "tokens_per_sec_ratio": round(ratio, 3),
        "token_identity": identical,
        "spill_pages_out": on_stats["kv_spill_pages_out"],
        "spill_pages_in": on_stats["kv_spill_pages_in"],
        "spill_on_sheds": on_stats["shed"],
        "spill_off_sheds": off_stats["shed"],
        # ON preserves (spills instead of destroying); OFF destroys.
        "spill_on_destructive_evictions": on_mgr["evictions"],
        "spill_off_destructive_evictions": off_mgr["evictions"],
        "ttft_resumed_ms": round(resumed_ttft * 1e3, 2),
        "ttft_cold_ms": round(cold_ttft * 1e3, 2),
        "ttft_resumed_vs_cold": round(
            resumed_ttft / cold_ttft, 3) if cold_ttft else 0.0,
        # CPU prefill is compute-trivial at this scale, so neither the
        # throughput ratio nor the TTFT gap says what the chip would
        # show (prefill is the quadratic term re-import removes).
        **({} if on_tpu else {"cpu_compute_bound_note": True}),
    }


def _bench_multichip_serving(spec, rng, cfg, on_tpu, DecodeEngine):
    """Multi-chip serving probe: sharded-vs-single delivered tok/s and
    TTFT at mesh 1/2/4, plus a KV-handoff latency histogram.

    Mesh sweep: one closed-loop burst per mesh size over otherwise
    identical engines (params + paged pool placed by
    serving/sharding.py; sizes above jax.device_count() are skipped —
    run with --fake-devices 4 for the hermetic sweep).  On the CPU
    box BOTH phases are compute-bound and XLA's host "collectives"
    are memcpy loops, so tensor parallelism cannot win here — the
    sweep proves token-identity and records the dispatch overhead;
    the HBM-bound decode roofline that TP actually multiplies exists
    only on real chips (same caveat discipline as the paged-KV
    probe's cpu_compute_bound_note).

    Handoff: prefill_export -> import round trips between two
    engines, recording export/import latency percentiles and
    per-page cost — the disaggregation tax a prefill/decode split
    pays per request."""
    import jax
    import numpy as np

    from kubeflow_tpu.serving import sharding

    ndev = jax.device_count()
    if on_tpu:
        prompt_lens, probe_new = [64, 128, 224], 64
        slots, prefill, block, n_req = 8, 256, 16, 24
        handoff_reps = 12
    else:
        prompt_lens, probe_new = [8, 16, 24], 16
        slots, prefill, block, n_req = 4, 32, 4, 12
        handoff_reps = 8
    mesh_sizes = [1] + [m for m in (2, 4) if m <= ndev]
    prompts = [
        rng.randint(1, cfg.vocab_size,
                    size=(prompt_lens[i % len(prompt_lens)],)
                    ).astype(np.int32)
        for i in range(n_req)
    ]

    def run_mesh(m):
        import threading

        mesh = sharding.build_mesh({"tensor": m}) if m > 1 else None
        eng = DecodeEngine(
            spec["cfg"], spec["params"], spec["decode"], slots=slots,
            prefill_len=prefill, kv_block_tokens=block,
            prefill_chunk_tokens=block * 2, mesh=mesh,
            name=f"mc-mesh{m}")
        tokens_out = []
        ttfts = []
        lock = threading.Lock()
        try:
            eng.submit({"tokens": prompts[0],
                        "max_new_tokens": probe_new})  # warm compile

            def client(p):
                out = eng.submit({"tokens": p,
                                  "max_new_tokens": probe_new,
                                  "return_timing": True})
                with lock:
                    tokens_out.append(
                        out["tokens"].shape[1] - p.shape[0])
                    ttfts.append(out["ttft_s"])

            threads = [threading.Thread(target=client, args=(p,))
                       for p in prompts]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            first = eng.submit({"tokens": prompts[0]})["tokens"]
        finally:
            eng.close()
        return {
            "mesh_devices": m,
            "tokens_per_sec": round(sum(tokens_out) / wall, 1)
            if wall else 0.0,
            "ttft_p50_ms": _pct_ms(ttfts, 0.50),
            "ttft_p99_ms": _pct_ms(ttfts, 0.99),
        }, first[0].tolist()

    sweep = []
    reference_tokens = None
    identical = True
    for m in mesh_sizes:
        record, toks = run_mesh(m)
        sweep.append(record)
        if reference_tokens is None:
            reference_tokens = toks
        elif toks != reference_tokens:
            identical = False
    base = sweep[0]["tokens_per_sec"]

    # --- handoff latency: export on one engine, import on another ---
    pre = DecodeEngine(spec["cfg"], spec["params"], spec["decode"],
                       slots=2, prefill_len=prefill,
                       kv_block_tokens=block, name="mc-handoff-pre")
    dec = DecodeEngine(spec["cfg"], spec["params"], spec["decode"],
                       slots=2, prefill_len=prefill,
                       kv_block_tokens=block, name="mc-handoff-dec")
    export_s, import_s, pages = [], [], 0
    try:
        p = prompts[2]
        # Warm round trip outside the timed loop: the first export
        # compiles the page gather and the first import the kv_import
        # program — seconds of XLA that would masquerade as p95.
        warm = pre.prefill_export({"tokens": p}).get("kv_handoff")
        if warm is not None:
            dec.submit({"tokens": p, "kv_handoff": warm,
                        "max_new_tokens": 1})
        for _ in range(handoff_reps):
            t0 = time.perf_counter()
            out = pre.prefill_export({"tokens": p})
            t1 = time.perf_counter()
            ho = out.get("kv_handoff")
            if ho is None:
                break
            pages = ho["k"].shape[1] if not isinstance(ho["k"], dict) \
                else ho["k"]["values"].shape[1]
            dec.submit({"tokens": p, "kv_handoff": ho,
                        "max_new_tokens": 1})
            import_s.append(time.perf_counter() - t1)
            export_s.append(t1 - t0)
    finally:
        pre.close()
        dec.close()
    return {
        "mesh_sweep": sweep,
        "sharded_vs_single": {
            f"mesh{r['mesh_devices']}": round(
                r["tokens_per_sec"] / base, 3) if base else 0.0
            for r in sweep[1:]},
        "tokens_identical_across_meshes": identical,
        "handoff_pages_per_request": pages,
        # Import includes the uncovered final chunk + one sampled
        # token (the decode tier's real admission cost); export is
        # the pure page gather off the prefill tier's pool.
        "handoff_export_ms_p50": _pct_ms(export_s, 0.50),
        "handoff_export_ms_p95": _pct_ms(export_s, 0.95),
        "handoff_import_ms_p50": _pct_ms(import_s, 0.50),
        "handoff_import_ms_p95": _pct_ms(import_s, 0.95),
        "handoff_round_trips": len(export_s),
        **({} if on_tpu else {
            "cpu_compute_bound_note":
                "CPU decode is compute-bound and host 'collectives' "
                "are memcpy loops, so the sharded engines measure "
                "SPMD dispatch overhead, not the HBM-roofline win "
                "tensor parallelism buys on real chips; the sweep's "
                "token-identity result is the acceptance signal "
                "here"}),
    }


def _bench_tracing_overhead(spec, rng, cfg, on_tpu, DecodeEngine):
    """Tracing overhead probe: the same concurrent decode window with
    the tracer DISABLED (the library default — what the headline
    engine windows above already run under) and ENABLED with every
    request traced (worst case: sample_rate 1.0, so no span record is
    skipped).  Windows interleave off/on so a one-sided stall cannot
    fake a regression; the capability estimate per side is its best
    window.  The acceptance claim is the DISABLED side: tracing off
    must add no measurable per-step overhead (the engine's only
    disabled-path cost is one None check per drain site), so the
    headline tok/s stays within noise of the pre-tracing baseline.
    """
    import threading

    import numpy as np

    from kubeflow_tpu.runtime import tracing

    n_requests, new, prompt_len, windows = (
        (16, 32, 16, 2) if on_tpu else (12, 12, 8, 2))
    prompts = [rng.randint(1, cfg.vocab_size,
                           size=(1, prompt_len)).astype(np.int32)
               for _ in range(n_requests)]

    def run_window(engine, traced):
        def client(prompt):
            if traced:
                span = tracing.start_span("bench.request")
                with tracing.use_span(span):
                    engine.submit({"tokens": prompt,
                                   "max_new_tokens": new})
                span.end()
            else:
                engine.submit({"tokens": prompt,
                               "max_new_tokens": new})

        threads = [threading.Thread(target=client, args=(p,))
                   for p in prompts]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return n_requests * new / (time.perf_counter() - t0)

    def make_engine(label):
        engine = DecodeEngine(
            spec["cfg"], spec["params"], spec["decode"], slots=4,
            prefill_len=max(32, prompt_len),
            name=f"bench-trace-{label}")
        engine.submit({"tokens": prompts[0], "max_new_tokens": 2})
        return engine

    off_engine = make_engine("off")
    on_engine = make_engine("on")
    off_rates, on_rates = [], []
    try:
        for _ in range(windows):
            tracing.disable()
            off_rates.append(run_window(off_engine, traced=False))
            tracing.enable(sample_rate=1.0, capacity=64)
            try:
                on_rates.append(run_window(on_engine, traced=True))
            finally:
                tracing.disable()
    finally:
        off_engine.close()
        on_engine.close()
    off_tok_s, on_tok_s = max(off_rates), max(on_rates)
    ratio = on_tok_s / off_tok_s if off_tok_s else 0.0
    print(f"tracing overhead: {off_tok_s:.1f} tok/s off vs "
          f"{on_tok_s:.1f} on (every request traced), on/off "
          f"{ratio:.3f}", file=sys.stderr)
    return {
        "tokens_per_sec_tracing_off": round(off_tok_s, 1),
        "tokens_per_sec_tracing_on": round(on_tok_s, 1),
        "on_vs_off": round(ratio, 3),
        "requests": n_requests,
        "sample_rate_on": 1.0,
    }


def _bench_speculative(spec, rng, cfg, on_tpu, DecodeEngine):
    """Speculative-decoding probe: n-gram drafting + batched verify
    (engine ``speculative_tokens``), spec ON vs OFF on otherwise
    identical engines (both sync_lag 0 — speculation forces a
    synchronous loop, so the OFF control pays the same read
    discipline and the delta is speculation alone).

    Two workloads:
      * high-acceptance — repetitive pattern-tiled prompts whose
        greedy continuations the drafter can predict.  Candidates are
        scored BEFORE timing by simulating the drafter against the
        reference continuations (host-only), and the most draftable
        ones are kept: the probe characterizes the high-acceptance
        regime, not prompt luck.  Acceptance bound: ON >= 1.3x OFF
        delivered tok/s.
      * low-acceptance — random prompts with short budgets, where the
        drafter should stay silent and the adaptive gates (per-slot
        width backoff, batch mass gate, measured-throughput gate)
        must hold ON ~at OFF (no-regression bound; a few percent of
        scheduling noise on a GIL-shared CPU box).

    Windows interleave ON/OFF with alternating order (ordering bias
    measured ~2% on the smoke box) and the max window is the
    capability estimate, as everywhere else in this bench.
    """
    import dataclasses
    import threading

    import numpy as np

    from kubeflow_tpu.models.generate import generate
    from kubeflow_tpu.serving.engine import _ngram_propose

    if on_tpu:
        slots, k, windows, workers = 4, 6, 2, 4
        pat_w, reps, probe_new = 8, 8, 128
        prefill, n_high, n_low, low_new = 64, 24, 32, 8
    else:
        slots, k, windows, workers = 2, 6, 3, 2
        pat_w, reps, probe_new = 4, 4, 96
        prefill, n_high, n_low, low_new = 16, 12, 32, 8
    # The probe owns its completion budget (longer runs amortize the
    # per-request draft warm-up), so it rides its own decode config
    # clamped to the model's real room.
    probe_new = min(probe_new, cfg.max_seq_len - prefill)
    decode = dataclasses.replace(spec["decode"],
                                 max_new_tokens=probe_new)

    def sim_gain(prompt, cont):
        """Drafter simulation against a known continuation: net tokens
        speculation would save (accepted minus verify rounds)."""
        hist = list(prompt) + [cont[0]]
        gained = rounds = 0
        i = 1
        while i < len(cont):
            room = len(cont) - i - 1
            prop = (_ngram_propose(np.asarray(hist, np.int32),
                                   min(k, room))
                    if room > 0 else np.empty((0,), np.int32))
            a = 0
            for j, p in enumerate(prop.tolist()):
                if p == cont[i + j]:
                    a += 1
                else:
                    break
            gained += a
            emitted = a + 1
            hist.extend(cont[i:i + emitted])
            i += emitted
            rounds += 1
        return gained - rounds

    cand = [np.tile(rng.randint(1, cfg.vocab_size, size=(pat_w,)),
                    reps).astype(np.int32) for _ in range(2 * n_high)]
    refs = np.asarray(generate(cfg, spec["params"], np.stack(cand),
                               decode)[0])
    plen = pat_w * reps
    ranked = sorted(
        range(len(cand)),
        key=lambda i: sim_gain(cand[i].tolist(),
                               refs[i, plen:].tolist()),
        reverse=True)
    high = [cand[i] for i in ranked[:n_high]]
    low = [rng.randint(1, cfg.vocab_size, size=(plen,)).astype(np.int32)
           for _ in range(n_low)]

    def make_engine(spec_tokens, label):
        engine = DecodeEngine(
            spec["cfg"], spec["params"], decode, slots=slots,
            prefill_len=prefill, prefill_chunk_tokens=prefill,
            prefix_caching=False, sync_lag=0,
            speculative_tokens=spec_tokens,
            name=f"bench-spec-{label}")
        # Warm every program OUTSIDE the timed windows: one repetitive
        # prompt drafts (chunked prefill + verify), one random prompt
        # decodes (step).
        engine.submit({"tokens": np.tile(
            rng.randint(1, cfg.vocab_size, size=(pat_w,)),
            reps).astype(np.int32), "max_new_tokens": 12})
        engine.submit({"tokens": rng.randint(
            1, cfg.vocab_size, size=(pat_w,)).astype(np.int32),
            "max_new_tokens": 2})
        return engine

    def window(engine, prompts, new):
        sem = threading.Semaphore(workers)

        def client(prompt):
            with sem:
                engine.submit({"tokens": prompt, "max_new_tokens": new})

        threads = [threading.Thread(target=client, args=(p,))
                   for p in prompts]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return len(prompts) * new / (time.perf_counter() - t0)

    def compare(prompts, new, label):
        on_engine = make_engine(k, f"{label}-on")
        off_engine = make_engine(0, f"{label}-off")
        on_rates, off_rates = [], []
        try:
            for w in range(windows):
                first, second = ((on_engine, off_engine) if w % 2 == 0
                                 else (off_engine, on_engine))
                rate1 = window(first, prompts, new)
                rate2 = window(second, prompts, new)
                if first is on_engine:
                    on_rates.append(rate1)
                    off_rates.append(rate2)
                else:
                    off_rates.append(rate1)
                    on_rates.append(rate2)
            return (max(on_rates), max(off_rates),
                    on_engine.stats(), off_engine.stats(),
                    on_engine.compiled_programs())
        finally:
            on_engine.close()
            off_engine.close()

    on_tok_s, off_tok_s, on_stats, off_stats, programs = compare(
        high, probe_new, "high")
    speedup = on_tok_s / off_tok_s if off_tok_s else 0.0
    lo_on, lo_off, lo_stats, _, _ = compare(low, low_new, "low")
    lo_ratio = lo_on / lo_off if lo_off else 0.0
    print(f"speculative: high-acceptance ON {on_tok_s:.1f} tok/s vs "
          f"OFF {off_tok_s:.1f} ({speedup:.2f}x), acceptance "
          f"{on_stats['spec_acceptance_rate']}, accepted/step "
          f"{on_stats['accepted_per_step']}; low-acceptance ratio "
          f"{lo_ratio:.2f} ({lo_stats['spec_drafted']} drafted)",
          file=sys.stderr)
    return {
        "draft_tokens": k,
        "slots": slots,
        "windows": windows,
        "probe_new_tokens": probe_new,
        "acceptance_rate": on_stats["spec_acceptance_rate"],
        "accepted_per_step": on_stats["accepted_per_step"],
        "drafted": on_stats["spec_drafted"],
        "accepted": on_stats["spec_accepted"],
        "verify_steps": on_stats["spec_steps"],
        "tok_s_spec_on": round(on_tok_s, 1),
        "tok_s_spec_off": round(off_tok_s, 1),
        "speedup": round(speedup, 3),
        "inter_token_gap_p50_ms_spec_on":
            on_stats["inter_token_gap_p50_ms"],
        "inter_token_gap_p50_ms_spec_off":
            off_stats["inter_token_gap_p50_ms"],
        "compiled_programs_spec_on": programs,
        "low_acceptance": {
            "tok_s_spec_on": round(lo_on, 1),
            "tok_s_spec_off": round(lo_off, 1),
            "ratio": round(lo_ratio, 3),
            "drafted": lo_stats["spec_drafted"],
            "accepted": lo_stats["spec_accepted"],
        },
    }


def _bench_fused_decode(spec, rng, cfg, on_tpu, DecodeEngine):
    """Fused-decode probe: device-resident multi-step rounds
    (``decode_rounds``, docs §5.2e) vs the per-step dispatch loop.

    Two measurements:

      * dispatch_overhead — raw AOT programs, no engine: at batch
        width 1/4/8, run the same N×k decode steps as k dispatches of
        ``decode_step`` vs ONE ``decode_rounds`` dispatch per round.
        perf_counter brackets split each round into host-dispatch wall
        (time for the call(s) to return — async enqueue cost) and
        total wall including the final ``block_until_ready``.  The
        per-round delta unfused-minus-fused is the per-step dispatch
        tax the while_loop eliminates.
      * engine-level headline — fused (decode_rounds=8) vs unfused
        (decode_rounds=1) engines on the same seeded concurrent
        workload, interleaved windows (ordering-bias discipline from
        the speculation probe): delivered tok/s ratio, plus a
        token-IDENTITY check over the full request set (greedy fused
        decode must be bit-for-bit the per-step loop).

    On the CPU smoke box a decode step is compute-bound and XLA runs
    the while_loop body at the same per-step cost, so the engine
    ratio hovers near parity there — the number that moves is the
    dispatch-overhead fraction; on real accelerators the eliminated
    per-step host round trips multiply delivered tok/s (same caveat
    discipline as the paged-KV probe's cpu_compute_bound_note)."""
    import dataclasses
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from kubeflow_tpu.models.generate import (
        decode_rounds,
        decode_step,
        init_paged_state,
        prefill_chunk_into_slot,
    )

    k = 8
    if on_tpu:
        rounds_n, probe_new = 16, 64
        eng_slots, prefill, n_requests, windows, workers = 8, 64, 24, 2, 8
    else:
        rounds_n, probe_new = 6, 32
        eng_slots, prefill, n_requests, windows, workers = 4, 16, 12, 3, 4
    probe_new = min(probe_new, cfg.max_seq_len - prefill)
    dec = dataclasses.replace(spec["decode"], temperature=0.0,
                              eos_token=-1,
                              max_new_tokens=rounds_n * k + 1)

    # --- dispatch-overhead probe: raw programs, one pool per batch
    # width.  Budget rounds_n*k+1 and eos -1 keep every slot live for
    # the whole sweep, so fused rounds run full width (the early-exit
    # path is the tests' job; here both sides execute identical
    # step counts).
    bt = 16
    tb = cfg.max_seq_len // bt
    steps_room = min(rounds_n * k, cfg.max_seq_len - prefill - 1)
    sweep_rounds = max(1, steps_room // k)

    def dispatch_probe(b):
        state = init_paged_state(cfg, b, b * tb, bt)
        tables = np.arange(b * tb, dtype=np.int32).reshape(b, tb)
        for s in range(b):
            prompt = rng.randint(1, cfg.vocab_size,
                                 size=(1, prefill)).astype(np.int32)
            state, _ = prefill_chunk_into_slot(
                cfg, spec["params"], state, dec, prompt,
                np.int32(0), np.int32(prefill),
                np.int32(steps_room + 1), np.int32(s), np.int32(0),
                jnp.asarray(tables[s:s + 1]))
        tab = jnp.asarray(tables)
        step_exec = decode_step.lower(
            cfg, spec["params"], state, dec, 1, tab).compile()
        rounds_exec = decode_rounds.lower(
            cfg, spec["params"], state, dec, k, tab,
            np.int32(k)).compile()

        def timed(fused, st):
            dispatch = total = 0.0
            for _ in range(sweep_rounds):
                t0 = time.perf_counter()
                if fused:
                    st, toks, _, _ = rounds_exec(
                        spec["params"], st, tab, np.int32(k))
                else:
                    for _ in range(k):
                        st, toks = step_exec(spec["params"], st, tab)
                dispatch += time.perf_counter() - t0
                jax.block_until_ready(toks)
                total += time.perf_counter() - t0
            return st, dispatch, total

        # Warm each executable on its own fresh copy (a shared warmup
        # state would arrive at the fused warm already done and
        # early-exit without ever running the loop body).
        timed(False, jax.tree_util.tree_map(lambda x: x.copy(), state))
        timed(True, jax.tree_util.tree_map(lambda x: x.copy(), state))
        st = jax.tree_util.tree_map(lambda x: x.copy(), state)
        st, unf_disp, unf_total = timed(False, st)
        st = jax.tree_util.tree_map(lambda x: x.copy(), state)
        st, fus_disp, fus_total = timed(True, st)
        per_round = 1000.0 / sweep_rounds
        return {
            "rounds": sweep_rounds,
            "steps_per_round": k,
            "unfused_ms_per_round": round(unf_total * per_round, 3),
            "fused_ms_per_round": round(fus_total * per_round, 3),
            "unfused_dispatch_ms_per_round":
                round(unf_disp * per_round, 3),
            "fused_dispatch_ms_per_round":
                round(fus_disp * per_round, 3),
            # The per-step dispatch tax fusing eliminates, as a
            # fraction of the unfused round.
            "dispatch_overhead_fraction": round(
                max(0.0, unf_total - fus_total) / unf_total, 3)
            if unf_total else 0.0,
            "fused_round_speedup": round(unf_total / fus_total, 3)
            if fus_total else 0.0,
        }

    overhead = {f"batch_{b}": dispatch_probe(b) for b in (1, 4, 8)}

    # --- engine-level headline: fused vs unfused engines, same
    # seeded request set, interleaved windows.
    eng_dec = dataclasses.replace(spec["decode"],
                                  max_new_tokens=probe_new)
    prompts = [rng.randint(1, cfg.vocab_size,
                           size=(prefill,)).astype(np.int32)
               for _ in range(n_requests)]

    def make_engine(rounds, label):
        engine = DecodeEngine(
            spec["cfg"], spec["params"], eng_dec, slots=eng_slots,
            prefill_len=prefill, prefill_chunk_tokens=prefill,
            prefix_caching=False, sync_lag=0, decode_rounds=rounds,
            name=f"bench-fused-{label}")
        engine.submit({"tokens": prompts[0], "max_new_tokens": 4})
        return engine

    def window(engine):
        sem = threading.Semaphore(workers)

        def client(prompt):
            with sem:
                engine.submit({"tokens": prompt,
                               "max_new_tokens": probe_new})

        threads = [threading.Thread(target=client, args=(p,))
                   for p in prompts]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return n_requests * probe_new / (time.perf_counter() - t0)

    fused_engine = make_engine(k, "on")
    plain_engine = make_engine(1, "off")
    fused_rates, plain_rates = [], []
    try:
        for w in range(windows):
            first, second = ((fused_engine, plain_engine) if w % 2 == 0
                             else (plain_engine, fused_engine))
            r1, r2 = window(first), window(second)
            if first is fused_engine:
                fused_rates += [r1]
                plain_rates += [r2]
            else:
                plain_rates += [r1]
                fused_rates += [r2]
        # Token identity over the whole request set, OUTSIDE the timed
        # windows: greedy fused decode is bit-for-bit the k=1 loop.
        identical = all(
            np.array_equal(
                fused_engine.submit({"tokens": p,
                                     "max_new_tokens": probe_new}
                                    )["tokens"],
                plain_engine.submit({"tokens": p,
                                     "max_new_tokens": probe_new}
                                    )["tokens"])
            for p in prompts[:4])
        fused_stats = fused_engine.stats()
        programs = fused_engine.compiled_programs()
    finally:
        fused_engine.close()
        plain_engine.close()

    fused_tok_s, plain_tok_s = max(fused_rates), max(plain_rates)
    speedup = fused_tok_s / plain_tok_s if plain_tok_s else 0.0
    print(f"fused decode: {fused_tok_s:.1f} tok/s fused(k={k}) vs "
          f"{plain_tok_s:.1f} unfused ({speedup:.2f}x), "
          f"{fused_stats['fused_rounds']} rounds, steps/round p50 "
          f"{fused_stats['steps_per_round_p50']}, batch-8 dispatch "
          f"overhead "
          f"{overhead['batch_8']['dispatch_overhead_fraction']}, "
          f"identity={'OK' if identical else 'FAIL'}",
          file=sys.stderr)
    return {
        "decode_rounds": k,
        "tok_s_fused": round(fused_tok_s, 1),
        "tok_s_unfused": round(plain_tok_s, 1),
        "speedup": round(speedup, 3),
        "tokens_identical": identical,
        "fused_rounds": fused_stats["fused_rounds"],
        "fused_steps_wasted": fused_stats["fused_steps_wasted"],
        "steps_per_round_p50": fused_stats["steps_per_round_p50"],
        "steps_per_round_p99": fused_stats["steps_per_round_p99"],
        "compiled_programs_fused": programs,
        "dispatch_overhead": overhead,
        **({} if on_tpu else {"cpu_compute_bound_note": True}),
    }


def _bench_adapter_array(spec, rng, cfg, on_tpu, DecodeEngine):
    """Adapter-array probe (§5.11): N per-tenant adapters CO-BATCHED on
    one engine (the stacked-delta array, one program set) vs the same
    tenants served as N per-model engines time-sharing the same fixed
    chip budget (each tenant's burst runs serially on a dedicated,
    pre-warmed engine — the world without adapter-array serving).

    The workload is the multi-tenant reality the serial path is worst
    at: each tenant brings a trickle of requests that UNDERFILLS the
    engine on its own, so the dedicated engines decode at low
    occupancy while the co-batched engine fills its slots with the
    tenants' mixed traffic.  Throughput counts delivered tokens over
    the identical request set; TTFT is client-observed.  Program
    compiles are warmed out of both timed windows (the serial side's
    N compile storms are a real deployment cost, but on the CPU box
    they would dwarf everything — the steady-state ratio is the
    honest signal).  Acceptance: co-batched greedy tokens IDENTICAL
    to each tenant's dedicated engine, and tok/s >= the serial path's
    (the occupancy win; the base-weight dedup that also multiplies
    capacity on real chips shows up as N x HBM here only in the
    resident-bytes arithmetic, not CPU wall time)."""
    import threading

    import numpy as np

    from kubeflow_tpu.serving.adapters import (
        AdapterRegistry,
        random_adapter_factors,
    )

    if on_tpu:
        n_adapters, per_tenant, probe_new = 4, 6, 64
        prompt_lens = [48, 96, 160]
        slots, prefill, block, adapter_rank = 16, 256, 16, 8
    else:
        n_adapters, per_tenant, probe_new = 3, 4, 16
        prompt_lens = [8, 14, 22]
        slots, prefill, block, adapter_rank = 8, 32, 4, 4
    tenants = [f"tenant{i}" for i in range(n_adapters)]
    factors = {name: random_adapter_factors(
        cfg, adapter_rank, seed=300 + i, scale=0.5)
        for i, name in enumerate(tenants)}
    # One request set shared verbatim by both paths: (tenant, prompt).
    workload = {
        name: [rng.randint(1, cfg.vocab_size,
                           size=(prompt_lens[j % len(prompt_lens)],)
                           ).astype(np.int32)
               for j in range(per_tenant)]
        for name in tenants
    }
    delivered = n_adapters * per_tenant * probe_new

    def burst(eng, reqs):
        """Closed-loop concurrent burst; returns (wall_s, ttfts,
        {(tenant, j): tokens})."""
        ttfts, outs = [], {}
        lock = threading.Lock()

        def client(name, j, p):
            out = eng.submit({"tokens": p, "adapter": name,
                              "max_new_tokens": probe_new,
                              "return_timing": True})
            with lock:
                ttfts.append(out["ttft_s"])
                outs[(name, j)] = np.asarray(
                    out["tokens"])[0].tolist()

        threads = [threading.Thread(target=client, args=r)
                   for r in reqs]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return time.perf_counter() - t0, ttfts, outs

    def make_engine(names, label):
        reg = AdapterRegistry(spec["cfg"], slots=n_adapters,
                              rank=adapter_rank, name=label)
        for name in names:
            reg.put(name, factors[name])
        eng = DecodeEngine(
            spec["cfg"], spec["params"], spec["decode"], slots=slots,
            prefill_len=prefill, kv_block_tokens=block,
            prefill_chunk_tokens=block * 2, adapters=reg, name=label)
        # Warm every program (and the tenant's stacked row) out of
        # the timed window.
        eng.submit({"tokens": workload[names[0]][0],
                    "adapter": names[0], "max_new_tokens": 2})
        return eng

    # --- co-batched: one engine, all tenants in one mixed burst ----
    eng = make_engine(tenants, "adapter-array")
    mixed = [(name, j, p) for name, prompts in workload.items()
             for j, p in enumerate(prompts)]
    try:
        co_wall, co_ttfts, co_outs = burst(eng, mixed)
        co_programs = eng.compiled_programs()
        co_stats = eng.stats()
    finally:
        eng.close()

    # --- serial per-model: N dedicated engines, one tenant's burst
    # each, time-sharing the chip (wall = sum of bursts). -----------
    serial_wall, serial_ttfts = 0.0, []
    serial_outs = {}
    for name in tenants:
        ded = make_engine([name], f"dedicated-{name}")
        try:
            wall, ttfts, outs = burst(
                ded, [(name, j, p)
                      for j, p in enumerate(workload[name])])
        finally:
            ded.close()
        serial_wall += wall
        serial_ttfts.extend(ttfts)
        serial_outs.update(outs)

    co_tok_s = delivered / co_wall if co_wall else 0.0
    serial_tok_s = delivered / serial_wall if serial_wall else 0.0
    return {
        "adapters": n_adapters,
        "requests_per_adapter": per_tenant,
        "adapter_rank": adapter_rank,
        "cobatched_tokens_per_sec": round(co_tok_s, 1),
        "serial_tokens_per_sec": round(serial_tok_s, 1),
        "cobatched_vs_serial": round(co_tok_s / serial_tok_s, 3)
        if serial_tok_s else 0.0,
        "cobatched_ttft_p50_ms": _pct_ms(co_ttfts, 0.50),
        "serial_ttft_p50_ms": _pct_ms(serial_ttfts, 0.50),
        "cobatched_ttft_p99_ms": _pct_ms(co_ttfts, 0.99),
        "serial_ttft_p99_ms": _pct_ms(serial_ttfts, 0.99),
        "tokens_identical_to_dedicated": co_outs == serial_outs,
        "cobatched_mean_occupancy": co_stats["mean_occupancy"],
        "compiled_programs": co_programs,
        "slots": slots,
        **({} if on_tpu else {
            "cpu_compute_bound_note":
                "CPU decode is compute-bound, so the co-batched win "
                "here is the occupancy gain alone; on real chips the "
                "serial path also pays N base-weight copies of HBM "
                "(or swap latency), which the stacked array removes — "
                "the token-identity result is the acceptance signal "
                "here"}),
    }


def bench_lm_engine(args, devices, n_chips, on_tpu):
    """Continuous-batching DecodeEngine vs the static BucketedLMBatcher
    on ONE mixed open-loop workload.

    The workload is the serving reality the static path is worst at:
    requests arrive on their own schedule (open loop, seeded arrival
    offsets), with mixed prompt lengths AND mixed per-request completion
    budgets.  The static batcher runs whole generate() programs — every
    request pays the export config's full max_new_tokens (the program
    bakes it in) and a request arriving mid-generation waits for the
    program to finish.  The engine admits into free slots between
    steps, retires rows the moment their budget is met, and treats the
    budget as data.  Throughput counts DELIVERED tokens (what clients
    asked for) over the same request set for both paths; the batcher's
    decoded-token rate is also recorded so the waste is explicit.

    Timing is the stall-resistant interleaved-window scheme from
    bench_lm_decode: engine/batcher windows alternate so one host
    stall cannot silently poison both sides, the faster window is the
    capability estimator, and per-window values ship in the record.
    """
    import tempfile
    import threading

    import jax
    import jax.numpy as jnp  # noqa: F401  (platform configured by caller)
    import numpy as np

    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.engine import DecodeEngine
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.model_server import (
        BucketedLMBatcher,
        ModelServer,
    )

    if on_tpu:
        overrides = {
            "vocab_size": 32_000, "d_model": 1024, "n_layers": 12,
            "n_heads": 8, "n_kv_heads": 8, "d_ff": 2816, "head_dim": 128,
            "max_seq_len": 2048, "dtype": "bfloat16",
        }
        max_new = 128
        prompt_lens = [32, 48, 64, 96, 128, 192, 256, 40]
        req_news = [16, 32, 64, 128]
        prefill_len, slots, spc, admit = 256, 16, 4, 4
        buckets = [64, 128, 256]
        n_requests, spread_s, windows = 64, 0.5, 2
    else:  # tiny hermetic config — runs under JAX_PLATFORMS=cpu
        overrides = {
            "vocab_size": 256, "d_model": 64, "n_layers": 2, "n_heads": 4,
            "n_kv_heads": 4, "d_ff": 128, "head_dim": 16,
            "max_seq_len": 128, "dtype": "float32",
        }
        max_new = 48
        prompt_lens = [4, 7, 11, 16, 23, 32, 27, 9]
        req_news = [4, 8, 16, 32]
        prefill_len, slots, spc, admit = 32, 8, 8, 4
        buckets = [8, 16, 32]
        n_requests, spread_s, windows = 64, 0.02, 3
    print(f"bench: lm engine vs static batcher, "
          f"d_model={overrides['d_model']} L{overrides['n_layers']}, "
          f"{n_requests} reqs, prompts {min(prompt_lens)}-"
          f"{max(prompt_lens)}, budgets {min(req_news)}-{max(req_news)} "
          f"of {max_new}, {devices[0].device_kind}", file=sys.stderr)

    cfg = _model_config(overrides)
    model = Transformer(cfg)
    rng = np.random.RandomState(0)
    variables = model.init(jax.random.key(0),
                           np.zeros((1, prompt_lens[0]), np.int32))
    with tempfile.TemporaryDirectory() as tmp:
        export(f"{tmp}/lm", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": max_new,
                       "temperature": 0.0})
        server = ModelServer()
        server.add_model("lm", f"{tmp}/lm")
        lm = server.get("lm")
        spec = lm.predict.engine_spec

        # One seeded request set + arrival schedule shared by BOTH
        # paths: (prompt, requested tokens, arrival offset).
        reqs = [
            (rng.randint(1, cfg.vocab_size,
                         size=(1, prompt_lens[i % len(prompt_lens)])
                         ).astype(np.int32),
             req_news[i % len(req_news)],
             rng.uniform(0.0, spread_s))
            for i in range(n_requests)
        ]
        delivered = sum(n for _, n, _ in reqs)

        window_failures = {}

        def run_window(submit, label):
            failures = []

            def client(prompt, new, delay):
                time.sleep(delay)
                try:
                    submit(prompt, new)
                except Exception as exc:  # noqa: BLE001 — recorded
                    failures.append((exc, new))

            threads = [threading.Thread(target=client, args=r)
                       for r in reqs]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            if failures:
                print(f"{label}: {len(failures)} failed requests "
                      f"({failures[0][0]})", file=sys.stderr)
            window_failures.setdefault(label, []).append(len(failures))
            # Failed submissions delivered nothing — their tokens must
            # not inflate the window's throughput.  ok_requests /
            # ok_delivered let the batcher's decoded-rate derivation
            # count only the requests that actually ran.
            ok = delivered - sum(n for _, n in failures)
            return {"rate": ok / wall, "ok_delivered": ok,
                    "ok_requests": n_requests - len(failures)}

        # --- engine: persistent across windows (the persistent cache
        # IS the design); warm all three programs with two tiny
        # requests.
        engine = DecodeEngine(
            spec["cfg"], spec["params"], spec["decode"], slots=slots,
            prefill_len=prefill_len, steps_per_call=spc,
            admit_width=admit, name="bench")
        for _ in range(2):
            engine.submit({"tokens": reqs[0][0],
                           "max_new_tokens": max(2, spc)})

        eng_ttfts = []  # client-observed TTFT (queue wait included)

        def engine_submit(prompt, new):
            out = engine.submit({"tokens": prompt,
                                 "max_new_tokens": new,
                                 "return_timing": True})
            eng_ttfts.append(out["ttft_s"])

        # --- static batcher: compile EVERY (bucket, allowed size)
        # generate program the windows can hit (the bench_lm_decode
        # lesson: promotion makes the bucket a batch-composition
        # property, so client-driven warmup cannot be trusted).
        allowed = [s for s in (1, 2, 4, 8, 16) if s <= slots]
        predict_fn = lm.predict
        for bucket in buckets:
            for size in allowed:
                warm = rng.randint(1, cfg.vocab_size,
                                   size=(size, bucket)).astype(np.int32)
                out = predict_fn({
                    "tokens": warm,
                    "prompt_len": np.full((size,), bucket, np.int32)})
                jax.block_until_ready(out["tokens"])

        def make_batcher():
            return BucketedLMBatcher(
                predict_fn, buckets=buckets, max_batch_size=slots,
                batch_timeout_s=0.02, allowed_batch_sizes=allowed,
                in_flight=2, name="bench-static")

        # --- interleaved windows (fresh batcher per window for clean
        # stats; the engine keeps its persistent cache).
        engine_windows, batcher_windows = [], []
        batcher_stats = None
        for _ in range(windows):
            engine_windows.append(run_window(engine_submit, "engine"))
            batcher = make_batcher()
            batcher_windows.append(run_window(
                lambda p, n: batcher.submit({"tokens": p}), "batcher"))
            batcher_stats = batcher.stats()
            batcher.close()
        engine_stats = engine.stats()
        compiled = engine.compiled_programs()
        engine.close()

        # --- shared-prefix probe: N clients sharing a 64-token system
        # prompt, prefix cache ON vs OFF on otherwise identical
        # engines.  TTFT with the cache ON should scale with the
        # UNCACHED SUFFIX length, not the full prompt — the acceptance
        # bound is ON >= 1.3x faster at p50.  Chunked prefill is active
        # on both sides (small chunk budget), so the OFF side also
        # measures that a long prompt admission arrives in bounded
        # chunks rather than one full-width stall.
        shared_prefix = _bench_shared_prefix(
            spec, rng, cfg, on_tpu, DecodeEngine)

        # --- speculation probe: n-gram drafting + batched verify on
        # repetitive (high-acceptance) and random (low-acceptance)
        # prompts, spec ON vs OFF.  Acceptance: ON >= 1.3x delivered
        # tok/s on the repetitive workload; the random workload must
        # hold ~at OFF (the adaptive gates' no-regression bound).
        speculative = _bench_speculative(
            spec, rng, cfg, on_tpu, DecodeEngine)

        # --- paged-KV capacity probe: mixed-length open loop at one
        # fixed block budget, tokens-resident admission vs the
        # slot-reserved capacity model.  Acceptance: >= 1.5x peak
        # concurrent in-flight at the same KV token budget, delivered
        # tok/s no worse.
        paged_kv = _bench_paged_kv(
            spec, rng, cfg, on_tpu, DecodeEngine)

        # --- tracing overhead probe: the distributed-tracing spine
        # (runtime/tracing.py) disabled vs enabled-and-traced on the
        # same workload.  Disabled must be free (the headline windows
        # above ran disabled); enabled costs only drain-time span
        # stamping.
        tracing_overhead = _bench_tracing_overhead(
            spec, rng, cfg, on_tpu, DecodeEngine)

        # --- multi-chip probe: mesh 1/2/4 sharded-vs-single tok/s +
        # TTFT (sizes above jax.device_count() skip — use
        # --fake-devices 4 for the hermetic sweep) and the
        # prefill/decode handoff latency histogram (§5.9).
        multichip_serving = _bench_multichip_serving(
            spec, rng, cfg, on_tpu, DecodeEngine)

        # --- fused-decode probe: decode_rounds while_loop rounds vs
        # the per-step dispatch loop — raw-program dispatch-overhead
        # brackets at batch 1/4/8 plus the engine-level delivered
        # tok/s ratio with a token-identity check (§5.2e).
        fused_decode = _bench_fused_decode(
            spec, rng, cfg, on_tpu, DecodeEngine)

        # --- hierarchical-KV probe: host spill tier ON vs OFF over
        # the same tight pool and multi-turn parked workload —
        # tokens addressable (5x HBM), delivered tok/s cost, and
        # resumed-vs-cold TTFT (§5.10).
        kv_spill = _bench_kv_spill(
            spec, rng, cfg, on_tpu, DecodeEngine)

        # --- adapter-array probe: N per-tenant adapters co-batched
        # on ONE engine (stacked deltas, one program set) vs N
        # dedicated per-model engines time-sharing the same chip —
        # delivered tok/s ratio, client TTFT, and a token-identity
        # check against each tenant's dedicated engine (§5.11).
        adapter_array = _bench_adapter_array(
            spec, rng, cfg, on_tpu, DecodeEngine)

    eng_rates = [w["rate"] for w in engine_windows]
    bat_rates = [w["rate"] for w in batcher_windows]
    eng_tok_s, bat_tok_s = max(eng_rates), max(bat_rates)
    bat_best = max(batcher_windows, key=lambda w: w["rate"])
    window_spread = (max(eng_rates) > 2 * min(eng_rates)
                     or max(bat_rates) > 2 * min(bat_rates))
    ratio = eng_tok_s / bat_tok_s if bat_tok_s else 0.0
    print(f"lm engine: {eng_tok_s:.1f} tok/s delivered vs static "
          f"batcher {bat_tok_s:.1f} ({ratio:.2f}x), occupancy "
          f"{engine_stats['mean_occupancy']}/{slots}, per-token p50 "
          f"{engine_stats['token_latency_p50_ms']} ms p95 "
          f"{engine_stats['token_latency_p95_ms']} ms", file=sys.stderr)
    return {
        "metric": "lm_engine_tokens_per_sec",
        "value": round(eng_tok_s, 1),
        "unit": "delivered tokens/sec (continuous batching, "
                "mixed open-loop)",
        "vs_baseline": round(ratio, 3),
        "detail": {
            "engine_tokens_per_sec": round(eng_tok_s, 1),
            "batcher_tokens_per_sec": round(bat_tok_s, 1),
            "engine_vs_batcher": round(ratio, 3),
            # The batcher's device-side rate: it decodes the full
            # config budget for every request no matter what was asked
            # (derived from its best window's SUCCESSFUL requests only).
            "batcher_decoded_tokens_per_sec": round(
                bat_tok_s * bat_best["ok_requests"] * max_new
                / bat_best["ok_delivered"], 1)
            if bat_best["ok_delivered"] else 0.0,
            "token_latency_p50_ms":
                engine_stats["token_latency_p50_ms"],
            "token_latency_p95_ms":
                engine_stats["token_latency_p95_ms"],
            "token_latency_p99_ms":
                engine_stats["token_latency_p99_ms"],
            # Client-observed TTFT (submit -> first token delivered,
            # queue wait included) across the open-loop windows, plus
            # the engine-side inter-token gap — the latency facts
            # delivered tok/s alone hides.
            "ttft_p50_ms": _pct_ms(eng_ttfts, 0.50),
            "ttft_p99_ms": _pct_ms(eng_ttfts, 0.99),
            "inter_token_gap_p50_ms":
                engine_stats["inter_token_gap_p50_ms"],
            "inter_token_gap_p99_ms":
                engine_stats["inter_token_gap_p99_ms"],
            "inter_token_gap_max_ms":
                engine_stats["inter_token_gap_max_ms"],
            "cached_token_ratio": engine_stats["cached_token_ratio"],
            "shared_prefix": shared_prefix,
            "speculative": speculative,
            "paged_kv": paged_kv,
            "tracing_overhead": tracing_overhead,
            "multichip_serving": multichip_serving,
            "fused_decode": fused_decode,
            "kv_spill": kv_spill,
            "adapter_array": adapter_array,
            "dispatch_overhead": fused_decode["dispatch_overhead"],
            "mean_slot_occupancy": engine_stats["mean_occupancy"],
            "slots": slots,
            "steps_per_call": spc,
            "admit_width": admit,
            "prefill_len": prefill_len,
            "engine_window_tokens_per_sec":
                [round(w, 1) for w in eng_rates],
            "batcher_window_tokens_per_sec":
                [round(w, 1) for w in bat_rates],
            **({"window_spread_suspect": True} if window_spread
               else {}),
            **({"window_failed_requests": window_failures}
               if any(n for fs in window_failures.values()
                      for n in fs) else {}),
            "batcher_mean_batch_size":
                (batcher_stats or {}).get("mean_batch_size"),
            "requests": n_requests,
            "prompt_lens": sorted(set(prompt_lens)),
            "requested_new_tokens": sorted(set(req_news)),
            "config_max_new_tokens": max_new,
            "delivered_tokens_per_window": delivered,
            "arrival_spread_s": spread_s,
            "compiled_programs": compiled,
            "d_model": overrides["d_model"],
            "n_layers": overrides["n_layers"],
            "device": devices[0].device_kind,
        },
    }


def bench_fleet(args, devices, n_chips, on_tpu):
    """Fleet router overhead + scale-out delivered throughput.

    Two questions the fleet control plane must answer with numbers:

      1. What does the router HOP cost?  Sequential closed-loop
         requests against one replica, first direct, then through the
         router (same replica, same process): the p50 delta is the
         router tax (target: < 10% of direct-path latency — the
         acceptance bound; the hop is one localhost round trip plus a
         JSON deadline parse).
      2. Does adding replicas add delivered tok/s?  The same
         concurrent open-loop burst through the router at 1 and then 3
         in-process replicas; delivered tokens/sec per fleet size and
         the 3-vs-1 scaling ratio.  In-process replicas share the GIL
         and the host's cores, so the hermetic CPU ratio UNDERSTATES
         on-metal scaling — the number that matters there is that the
         ratio exceeds 1 (the router actually spreads work); per-pod
         replicas on real accelerators scale by device count.
    """
    import http.client
    import json as _json
    import tempfile
    import threading

    import jax
    import numpy as np

    from kubeflow_tpu.fleet.endpoints import (
        Endpoint,
        EndpointRegistry,
        StaticEndpoints,
    )
    from kubeflow_tpu.fleet.router import FleetRouter, make_router_server
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.http import make_http_server
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.main import batcher_factory
    from kubeflow_tpu.serving.model_server import ModelServer

    if on_tpu:
        overrides = {
            "vocab_size": 32_000, "d_model": 1024, "n_layers": 12,
            "n_heads": 8, "n_kv_heads": 8, "d_ff": 2816,
            "head_dim": 128, "max_seq_len": 2048, "dtype": "bfloat16",
        }
        max_new, prompt_len, slots = 64, 64, 8
        seq_requests, burst_requests, clients = 24, 48, 8
    else:
        overrides = {
            "vocab_size": 256, "d_model": 64, "n_layers": 2,
            "n_heads": 4, "n_kv_heads": 4, "d_ff": 128, "head_dim": 16,
            "max_seq_len": 128, "dtype": "float32",
        }
        max_new, prompt_len, slots = 32, 8, 4
        seq_requests, burst_requests, clients = 16, 32, 8
    print(f"bench: fleet router, d_model={overrides['d_model']} "
          f"L{overrides['n_layers']}, {seq_requests} sequential + "
          f"{burst_requests}-request bursts, "
          f"{devices[0].device_kind}", file=sys.stderr)

    cfg = _model_config(overrides)
    model = Transformer(cfg)
    rng = np.random.RandomState(0)
    variables = model.init(jax.random.key(0),
                           np.zeros((1, prompt_len), np.int32))
    prompt = rng.randint(1, cfg.vocab_size,
                         size=(prompt_len,)).tolist()
    body = _json.dumps({"instances": [{"tokens": prompt}]}).encode()

    def make_replica(base):
        server = ModelServer()
        server.add_model("lm", base)
        server.enable_batching("lm", batcher_factory(
            micro_batch_size=0, batch_timeout_s=0.005,
            lm_engine=True, lm_engine_slots=slots,
            lm_engine_prefill_len=prompt_len))
        httpd, _ = make_http_server(server, port=0, host="127.0.0.1")
        return server, httpd

    class _Client:
        """Keep-alive client (both measured paths pay identical
        client-side costs; fresh-connection clients were measured to
        dominate the sub-10ms signal this bench exists to read)."""

        def __init__(self, port):
            self._port = port
            self._conn = None

        def predict(self):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    "127.0.0.1", self._port, timeout=600)
            try:
                self._conn.request("POST", "/model/lm:predict",
                                   body=body)
                resp = self._conn.getresponse()
                payload = _json.loads(resp.read())
                if resp.will_close:
                    self.close()
                return payload
            except Exception:
                self.close()
                raise

        def close(self):
            if self._conn is not None:
                self._conn.close()
                self._conn = None

    def predict(port):
        client = _Client(port)
        try:
            return client.predict()
        finally:
            client.close()

    def p50_of(port, n):
        client = _Client(port)
        lat = []
        try:
            client.predict()  # connection + route warm
            for _ in range(n):
                t0 = time.perf_counter()
                out = client.predict()
                lat.append(time.perf_counter() - t0)
                assert len(out["predictions"][0]["tokens"]) \
                    == prompt_len + max_new
        finally:
            client.close()
        lat.sort()
        return lat[len(lat) // 2]

    def burst_tokps(port, n_requests, n_clients):
        """Closed-loop client pool; delivered new tokens / wall."""
        errors = []
        done = []
        lock = threading.Lock()
        work = list(range(n_requests))

        def client():
            conn = _Client(port)
            try:
                while True:
                    with lock:
                        if not work:
                            return
                        work.pop()
                    try:
                        conn.predict()
                        done.append(1)
                    except Exception as exc:  # noqa: BLE001 — recorded
                        errors.append(exc)
            finally:
                conn.close()

        threads = [threading.Thread(target=client)
                   for _ in range(n_clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        return len(done) * max_new / wall, len(errors)

    replicas = []
    router_httpd = None
    registry = None
    with tempfile.TemporaryDirectory() as tmp:
        export(f"{tmp}/lm", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": max_new,
                       "temperature": 0.0})
        try:
            replicas = [make_replica(f"{tmp}/lm") for _ in range(3)]
            ports = [h.server_address[1] for _, h in replicas]
            # Warm every engine (compile outside every timed window).
            for port in ports:
                predict(port)

            # -- 1. router hop tax on one replica ---------------------
            direct_p50 = p50_of(ports[0], seq_requests)
            single = StaticEndpoints([Endpoint(
                name="r0", url=f"http://127.0.0.1:{ports[0]}")])
            registry = EndpointRegistry(single, probe_interval_s=0.5)
            registry.refresh()
            router = FleetRouter(registry, max_tries=3,
                                 try_timeout_s=600.0)
            router_httpd, _ = make_router_server(
                router, port=0, host="127.0.0.1")
            rport = router_httpd.server_address[1]
            router_p50 = p50_of(rport, seq_requests)
            overhead = (router_p50 - direct_p50) / direct_p50

            # -- 2. delivered tok/s at 1 -> 3 replicas ----------------
            tokps_1, err_1 = burst_tokps(rport, burst_requests,
                                         clients)
            fleet = StaticEndpoints([
                Endpoint(name=f"r{i}", url=f"http://127.0.0.1:{p}")
                for i, p in enumerate(ports)])
            registry.set_source(fleet)
            registry.refresh()
            tokps_3, err_3 = burst_tokps(rport, burst_requests,
                                         clients)
        finally:
            if router_httpd is not None:
                router_httpd.shutdown()
            for srv, httpd in replicas:
                httpd.shutdown()
                httpd.server_close()
                srv.stop()

    ratio = tokps_3 / tokps_1 if tokps_1 else 0.0
    return {
        "metric": "fleet_delivered_tokens_per_sec",
        "value": round(tokps_3, 1),
        "unit": "tok/s @ 3 replicas (router path)",
        "vs_baseline": round(ratio, 3),
        "detail": {
            "device": devices[0].device_kind,
            "direct_p50_ms": round(direct_p50 * 1e3, 2),
            "router_p50_ms": round(router_p50 * 1e3, 2),
            "router_overhead_frac": round(overhead, 4),
            "router_overhead_target": "< 0.10 of direct p50",
            "delivered_tokps_1_replica": round(tokps_1, 1),
            "delivered_tokps_3_replicas": round(tokps_3, 1),
            "scaling_ratio_3v1": round(ratio, 3),
            "failed_requests": err_1 + err_3,
            "requests_per_burst": burst_requests,
            "clients": clients,
            "max_new_tokens": max_new,
            "note": "in-process replicas share the GIL/cores: the "
                    "hermetic ratio understates per-pod scaling",
        },
    }


def bench_data(args, devices, n_chips, on_tpu):
    """KFTR input pipeline throughput: the default path vs the python
    decode/stack loop, at two record sizes.

    The pipeline default is the C++ core's in-core stacked-batch path
    (KTE1 decode + batch assembly in native code, loader.py
    stacked_batches): python cost is one FFI call per batch.  Raw record
    handout auto-selects the single-thread python reader on local files
    (memcpy-bound; the threaded core's per-record copy is a net loss
    there — the round-2 finding) and is reported for both readers,
    labeled for what each is.  All ratios are native/python: > 1 means
    the default (native) path wins.
    """
    import tempfile

    import numpy as np

    from kubeflow_tpu.data.loader import (RecordDataset, tensor_batches,
                                          write_example_shards)

    rng = np.random.RandomState(0)

    def pipeline_rates(paths, batch):
        out = {}
        for mode, kw in (("native", {}),
                         ("python", {"force_python": True})):
            best = 0.0
            for _ in range(2):
                ds = RecordDataset(paths, **kw)
                t0 = time.perf_counter()
                n = sum(b["label"].shape[0]
                        for b in tensor_batches(ds, batch))
                best = max(best, n / (time.perf_counter() - t0))
            out[mode] = best
        return out

    with tempfile.TemporaryDirectory() as tmp:
        base_image = rng.randn(64, 64, 3).astype(np.float32)
        img_paths = write_example_shards(
            ({"image": base_image, "label": np.int64(i % 1000)}
             for i in range(4096)),
            f"{tmp}/img", examples_per_shard=512)
        img = pipeline_rates(img_paths, 64)

        feat = rng.randn(32).astype(np.float32)
        small_paths = write_example_shards(
            ({"x": feat, "label": np.int64(i % 1000)}
             for i in range(100_000)),
            f"{tmp}/small", examples_per_shard=12_500)
        small = pipeline_rates(small_paths, 256)

        def raw_rate(**kw):
            t0 = time.perf_counter()
            n = sum(1 for _ in RecordDataset(img_paths, **kw))
            return n / (time.perf_counter() - t0)

        raw_default = raw_rate()               # auto: python reader
        raw_threaded = raw_rate(num_threads=4)  # explicit native core
    img_ratio = img["native"] / max(img["python"], 1e-9)
    small_ratio = small["native"] / max(small["python"], 1e-9)
    print(f"data: image pipeline native {img['native']:.0f} ex/s vs "
          f"python {img['python']:.0f} ({img_ratio:.2f}x); small-record "
          f"native {small['native']:.0f} vs python {small['python']:.0f} "
          f"({small_ratio:.2f}x); raw default {raw_default:.0f} rec/s, "
          f"threaded-native {raw_threaded:.0f}", file=sys.stderr)
    return {
        "metric": "kftr_pipeline_examples_per_sec",
        "value": round(img["native"], 1),
        "unit": "examples/sec (64x64x3 images, in-core decode+stack)",
        "vs_baseline": round(img_ratio, 2),
        "detail": {
            "pipeline_native_examples_per_sec": round(img["native"], 1),
            "pipeline_python_examples_per_sec": round(img["python"], 1),
            "native_vs_python_ratio": round(img_ratio, 2),
            "small_record_native_examples_per_sec":
                round(small["native"], 1),
            "small_record_python_examples_per_sec":
                round(small["python"], 1),
            "small_record_native_vs_python_ratio": round(small_ratio, 2),
            "raw_default_records_per_sec": round(raw_default, 1),
            "raw_threaded_native_records_per_sec": round(raw_threaded, 1),
            "raw_default_reader": "python single-thread (auto-selected "
                                  "on local files)",
        },
    }


def bench_hfta(args, devices, n_chips, on_tpu):
    """Horizontally fused training arrays (runtime/hfta.py): N small
    same-architecture jobs as ONE vmapped SPMD program vs the same N
    run sequentially as width-1 solo runs.

    Reports the aggregate-steps/s ratio (fused / sequential-solo) and
    the bit-identity flag — member i of the fused run must reproduce
    its width-1 control's final loss and params exactly, or the
    speedup is meaningless.  Timing excludes each run's compile by
    dropping the first on_step marks.  On CPU the win measures
    dispatch amortization on a compute-bound host, not TPU HBM/MXU
    behavior; cpu_compute_bound_note marks the record.
    """
    import os

    import jax
    import numpy as np

    from kubeflow_tpu.models.transformer import TransformerConfig, lm_task
    from kubeflow_tpu.parallel import MeshSpec
    from kubeflow_tpu.runtime.hfta import FusedTrainer, MemberSpec
    from kubeflow_tpu.runtime.metrics import MetricsLogger

    n_members = 4
    steps = 24 if on_tpu else 20
    warm = 4   # on_step marks dropped before timing (compile + settle)
    seq = 128 if on_tpu else 8
    batch = (8 if on_tpu else 2) * max(1, n_chips)
    # The HFTA regime is N jobs each too SMALL to fill the machine —
    # per-step fixed cost (dispatch, launch, collective setup) rivals
    # the math, which is exactly what fusing N steps into one program
    # amortizes.  A model sized to saturate the chip solo would show
    # ~1x and belongs in the lm benchmark instead.
    cfg = TransformerConfig(
        vocab_size=512 if on_tpu else 64,
        d_model=128 if on_tpu else 16,
        n_layers=2 if on_tpu else 1,
        n_heads=2, n_kv_heads=2,
        d_ff=512 if on_tpu else 32,
        head_dim=64 if on_tpu else 8,
        max_seq_len=seq, dtype="bfloat16" if on_tpu else "float32")
    mesh = MeshSpec(data=-1).build(devices)
    init_fn, loss_fn = lm_task(cfg, mesh=mesh)

    def data_factory():
        rng = np.random.RandomState(0)
        while True:
            yield {"tokens": rng.randint(
                0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)}

    def run(members):
        ft = FusedTrainer(
            init_fn=init_fn, loss_fn=loss_fn, members=members,
            mesh=mesh,
            metrics=MetricsLogger(stream=open(os.devnull, "w")))
        marks: list = []
        state = ft.fit(data_factory(), steps, log_every=10_000,
                       on_step=lambda i: marks.append(
                           time.perf_counter()))
        jax.block_until_ready(state.params)
        marks.append(time.perf_counter())
        tail = marks[warm:]
        return ft, state, (len(tail) - 1) / max(
            tail[-1] - tail[0], 1e-9)

    members = [MemberSpec(name=f"m{i}", seed=i, lr=1e-3 * (i + 1))
               for i in range(n_members)]
    fused_tr, fused_state, fused_stepps = run(members)
    fused_agg = fused_stepps * n_members

    solo_stepps: list = []
    identical = True
    for i, member in enumerate(members):
        solo_tr, solo_state, stepps = run([member])
        solo_stepps.append(stepps)
        a = jax.tree_util.tree_leaves(
            solo_tr.member_state(solo_state, 0).params)
        b = jax.tree_util.tree_leaves(
            fused_tr.member_state(fused_state, i).params)
        identical &= all(
            np.array_equal(np.asarray(x), np.asarray(y))
            for x, y in zip(a, b))
        name = member.name
        identical &= (solo_tr.last_metrics.get(f"loss/{name}")
                      == fused_tr.last_metrics.get(f"loss/{name}"))
    # Sequential-solo aggregate: N members share the wall clock, so
    # the fleet-level rate is the harmonic combination of the runs.
    seq_agg = n_members / sum(1.0 / s for s in solo_stepps)
    ratio = fused_agg / max(seq_agg, 1e-9)
    print(f"hfta: fused x{n_members} {fused_agg:.2f} member-steps/s "
          f"vs sequential solo {seq_agg:.2f} ({ratio:.2f}x), "
          f"bit-identical={identical}", file=sys.stderr)
    return {
        "detail": {
            "members": n_members,
            "steps_timed": steps - warm,
            "fused_aggregate_steps_per_s": round(fused_agg, 3),
            "sequential_solo_aggregate_steps_per_s": round(seq_agg, 3),
            "fused_vs_sequential_ratio": round(ratio, 2),
            "loss_trajectory_identical": bool(identical),
            **({} if on_tpu else {"cpu_compute_bound_note": True}),
        },
    }


def bench_colocation(args, devices, n_chips, on_tpu):
    """Elastic train/serve colocation (scheduler/colocate.py, user
    guide §5.13): one simulated diurnal cycle on ONE shared chip pool,
    beside the static split-pool baseline it replaces.

    The control plane is real — FakeKube + ClusterScheduler +
    TPUJobController + the fleet Autoscaler in claims mode — on an
    injected clock, so an 8 h phase costs microseconds of wall time.
    The morning burst writes a 2-replica serving claim that evicts the
    low-priority training gang on the SHORT serving grace; the evening
    trough releases the chips and training backfills.  Reported:

      * combined-pool utilization across the 24 h cycle (chip-seconds
        used / capacity), beside the static-partition counterfactual
        computed from the SAME demand curve — the split pool strands
        its serving half all night (acceptance: >= 0.85 colocated);
      * claim-grant latency in simulated seconds (dominated by the
        serving grace window the victim drains under) plus the wall
        cost of the whole control-plane transition;
      * bit-identity: the evicted job, resumed from its verified
        checkpoint, must FINISH with params identical to an
        uninterrupted control run — or the "elastic" story is silently
        corrupting training;
      * burst-phase serving p50/p99 from a closed-loop burst with
        deadline_ms on every request — the shed/deadline contract is
        zero 429/504 and p99 under the deadline.

    On CPU the serving latencies measure a compute-bound host, not TPU
    decode; cpu_compute_bound_note marks the record.
    """
    import http.client
    import json as _json
    import tempfile
    import threading

    import jax
    import numpy as np

    from kubeflow_tpu.fleet.autoscaler import Autoscaler
    from kubeflow_tpu.models.transformer import Transformer
    from kubeflow_tpu.operator import crd
    from kubeflow_tpu.operator.gang import GangScheduler
    from kubeflow_tpu.operator.kube import FakeKube
    from kubeflow_tpu.operator.reconciler import TPUJobController
    from kubeflow_tpu.runtime.checkpoint import CheckpointManager
    from kubeflow_tpu.scheduler import (
        LABEL_PRIORITY,
        LABEL_TENANT,
        ClusterScheduler,
        PreemptionConfig,
        SchedulerConfig,
        colocate,
    )
    from kubeflow_tpu.serving.export import export
    from kubeflow_tpu.serving.http import make_http_server
    from kubeflow_tpu.serving.loaders import _model_config
    from kubeflow_tpu.serving.main import batcher_factory
    from kubeflow_tpu.serving.model_server import ModelServer
    from kubeflow_tpu.testing import faults

    ns = "bench"
    slices, chips_per_slice = 4, 8
    cap = slices * chips_per_slice
    phase_s = 8 * 3600.0   # trough / burst / trough: a 24 h cycle
    drain_s = 6.0          # past the 5 s serving grace
    if on_tpu:
        overrides = {
            "vocab_size": 32_000, "d_model": 1024, "n_layers": 12,
            "n_heads": 8, "n_kv_heads": 8, "d_ff": 2816,
            "head_dim": 128, "max_seq_len": 2048, "dtype": "bfloat16",
        }
        max_new, prompt_len, slots_n = 64, 64, 8
        burst_requests, clients, deadline_ms = 48, 8, 10_000.0
    else:
        overrides = {
            "vocab_size": 256, "d_model": 64, "n_layers": 2,
            "n_heads": 4, "n_kv_heads": 4, "d_ff": 128, "head_dim": 16,
            "max_seq_len": 128, "dtype": "float32",
        }
        max_new, prompt_len, slots_n = 16, 8, 4
        burst_requests, clients, deadline_ms = 24, 4, 30_000.0
    print(f"bench: colocation diurnal cycle, pool {cap} chips, "
          f"{burst_requests}-request serving burst, "
          f"{devices[0].device_kind}", file=sys.stderr)

    total_steps, evict_after = 9, 5

    def train_step(w, step):
        # Any reordering/precision drift between the control run and
        # the resumed run breaks exact equality.
        return w * np.float32(1.0 + 2.0 ** -10) + np.float32(step)

    def train_cr(name, priority, n):
        job = crd.TPUJobSpec(name=name, namespace=ns, num_slices=n)
        cr = job.to_custom_resource()
        cr["metadata"]["labels"] = {LABEL_TENANT: "research",
                                    LABEL_PRIORITY: priority}
        return cr

    class _Load:
        """Registry stand-in scripting the diurnal curve."""

        load = 0.0

        def total_load(self):
            return self.load

        def ready_count(self):
            return 1

    # Demand curve (chips wanted per phase): training always wants the
    # whole pool; serving wants 2 replicas (16 chips) during the burst.
    # The static-partition counterfactual reserves half the pool per
    # side and can never trade — that is the number colocation exists
    # to beat.
    half = cap // 2
    static_segments = [(phase_s, min(cap, half) + 0),
                       (phase_s, min(cap, half) + min(
                           2 * chips_per_slice, half)),
                       (phase_s, min(cap, half) + 0)]

    segments = []   # (sim_seconds, used_chips) — the colocated pool
    base = np.arange(8, dtype=np.float32)
    with faults.injected("seed=20260807") as inj, \
            tempfile.TemporaryDirectory() as tmp:
        kube = FakeKube()
        kube.create_deployment({
            "metadata": {"name": "lm", "namespace": ns},
            "spec": {"replicas": 0}})
        gang = GangScheduler({"v5e-8": slices})
        cluster = ClusterScheduler(gang, SchedulerConfig(
            preemption=PreemptionConfig(
                grace_period_s=30.0, serving_grace_period_s=5.0)))
        ctl = TPUJobController(kube, gang, cluster)
        load = _Load()
        claims = colocate.ServingClaimClient(kube, ns, "lm")
        scaler = Autoscaler(
            kube, ns, "lm", load, target_inflight_per_replica=4.0,
            min_replicas=0, max_replicas=4,
            scale_up_cooldown_s=10.0, scale_down_cooldown_s=60.0,
            claims=claims)

        def job_statuses():
            return {c["metadata"]["name"]: (c.get("status") or {})
                    for c in kube.list_custom(ns)}

        # -- night trough: training owns the whole pool ---------------
        scaler.reconcile_once()
        kube.create_custom(train_cr("night-batch", "low", 2))
        kube.create_custom(train_cr("steady", "normal", 2))
        ctl.reconcile_all()
        w = base.copy()
        with CheckpointManager(f"{tmp}/ckpt",
                               save_interval_steps=1) as mgr:
            for step in range(evict_after):
                w = train_step(w, step)
                mgr.save(step, {"step": np.full((), step, np.int32),
                                "w": w})
        for i, p in enumerate(kube.list_pods(
                ns, labels={"kubeflow-tpu.org/job-name":
                            "night-batch"})):
            kube.set_pod_node(ns, p["metadata"]["name"], f"node-{i}")
        segments.append((phase_s, cluster.pool_status()["used_chips"]))
        inj.advance_clock(phase_s)

        # -- morning burst: the claim steals chips --------------------
        wall0 = time.perf_counter()
        load.load = 8.0   # ceil(8/4) = 2 replicas wanted
        scaler.reconcile_once()   # writes the 2-replica claim CR
        ctl.reconcile_all()       # victim drains; prepull pods pin up
        prepulls = len(kube.list_pods(
            ns, labels={colocate.LABEL_WORKLOAD:
                        colocate.WORKLOAD_PREPULL}))
        # The victim holds its chips through the SHORT drain window
        # (the 30 s training grace would still be holding it at 6 s).
        segments.append((drain_s,
                         cluster.pool_status()["used_chips"]))
        inj.advance_clock(drain_s)
        granted = False
        for _ in range(6):
            ctl.reconcile_all()
            claim_st = job_statuses().get(
                colocate.claim_name("lm"), {})
            if claim_st.get("grantedReplicas") == 2:
                granted = True
                break
        wall_grant_ms = (time.perf_counter() - wall0) * 1e3
        assert granted, f"claim never granted: {job_statuses()}"
        assert kube.get_deployment(
            ns, "lm")["spec"]["replicas"] == 2
        pool = cluster.pool_status()
        serving_chips = pool["serving_chips"]
        segments.append((phase_s - drain_s, pool["used_chips"]))

        # -- burst-phase serving latency: the shed/deadline contract --
        cfg = _model_config(overrides)
        model = Transformer(cfg)
        rng = np.random.RandomState(0)
        variables = model.init(jax.random.key(0),
                               np.zeros((1, prompt_len), np.int32))
        prompt = rng.randint(1, cfg.vocab_size,
                             size=(prompt_len,)).tolist()
        body = _json.dumps({
            "deadline_ms": deadline_ms,
            "instances": [{"tokens": prompt}]}).encode()
        export(f"{tmp}/lm-model", 1, variables,
               loader="kubeflow_tpu.serving.loaders:lm_generate",
               config={"model": overrides, "max_new_tokens": max_new,
                       "temperature": 0.0})
        httpd = None
        server = None
        try:
            server = ModelServer()
            server.add_model("lm", f"{tmp}/lm-model")
            server.enable_batching("lm", batcher_factory(
                micro_batch_size=0, batch_timeout_s=0.005,
                lm_engine=True, lm_engine_slots=slots_n,
                lm_engine_prefill_len=prompt_len))
            httpd, _ = make_http_server(server, port=0,
                                        host="127.0.0.1")
            port = httpd.server_address[1]

            def one(conn):
                conn.request("POST", "/model/lm:predict", body=body)
                resp = conn.getresponse()
                resp.read()
                return resp.status

            lock = threading.Lock()
            work = list(range(burst_requests))
            outcomes = []

            def client_loop():
                conn = http.client.HTTPConnection(
                    "127.0.0.1", port, timeout=600)
                try:
                    while True:
                        with lock:
                            if not work:
                                return
                            work.pop()
                        t0 = time.perf_counter()
                        try:
                            status = one(conn)
                        except Exception:  # noqa: BLE001 — recorded
                            outcomes.append((0, 0.0))
                            conn.close()
                            conn = http.client.HTTPConnection(
                                "127.0.0.1", port, timeout=600)
                            continue
                        outcomes.append(
                            (status, time.perf_counter() - t0))
                finally:
                    conn.close()

            warm = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=600)
            assert one(warm) == 200  # compile outside the timed burst
            warm.close()
            threads = [threading.Thread(target=client_loop)
                       for _ in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            if httpd is not None:
                httpd.shutdown()
                httpd.server_close()
            if server is not None:
                server.stop()
        lat = sorted(s for code, s in outcomes if code == 200)
        sheds = sum(1 for code, _ in outcomes if code == 429)
        expired = sum(1 for code, _ in outcomes if code == 504)
        errors = sum(1 for code, _ in outcomes
                     if code not in (200, 429, 504))
        p50 = lat[len(lat) // 2] if lat else 0.0
        p99 = lat[min(len(lat) - 1,
                      int(0.99 * len(lat)))] if lat else 0.0
        contract_ok = bool(lat and sheds == 0 and expired == 0
                           and errors == 0
                           and p99 * 1e3 <= deadline_ms)
        inj.advance_clock(phase_s - drain_s)

        # -- evening trough: release, backfill, bit-identical resume --
        load.load = 0.0
        scaler.reconcile_once()   # deletes the claim, zeroes replicas
        ctl.reconcile_all()       # stale sweep frees the gang claim
        ctl.reconcile_all()       # backfill re-admits the victim
        victim = job_statuses().get("night-batch", {})
        victim_restarts = int(victim.get("restarts", 0) or 0)
        victim_preemptions = int(victim.get("preemptions", 0) or 0)
        segments.append((phase_s,
                         cluster.pool_status()["used_chips"]))
        fresh = {"step": np.zeros((), np.int32),
                 "w": np.zeros(8, np.float32)}
        with CheckpointManager(f"{tmp}/ckpt") as mgr2:
            restored, start = mgr2.restore_or_init(fresh)
        resumed = restored["w"]
        for step in range(start, total_steps):
            resumed = train_step(resumed, step)
        control = base.copy()
        for step in range(total_steps):
            control = train_step(control, step)
        bit_identical = bool(start == evict_after
                             and np.array_equal(resumed, control))
        claims.close()

    total_s = sum(d for d, _ in segments)
    util = sum(d * u for d, u in segments) / (cap * total_s)
    static_total = sum(d for d, _ in static_segments)
    static_util = sum(d * u for d, u in static_segments) \
        / (cap * static_total)
    print(f"colocation: pool util {util:.3f} colocated vs "
          f"{static_util:.3f} static split, claim grant "
          f"{drain_s:.1f}s sim ({wall_grant_ms:.0f}ms wall), "
          f"resume bit-identical={bit_identical}, burst p50 "
          f"{p50 * 1e3:.1f}ms p99 {p99 * 1e3:.1f}ms (sheds={sheds}, "
          f"expired={expired})", file=sys.stderr)
    return {
        "metric": "colocation_pool_utilization",
        "value": round(util, 4),
        "unit": "chip-seconds used / capacity, 24h diurnal cycle",
        "vs_baseline": round(util / max(static_util, 1e-9), 3),
        "detail": {
            "device": devices[0].device_kind,
            "combined_pool_utilization": round(util, 4),
            "static_partition_utilization": round(static_util, 4),
            "utilization_target": ">= 0.85 colocated",
            "utilization_ok": bool(util >= 0.85),
            "pool_capacity_chips": cap,
            "burst_serving_chips": serving_chips,
            "claim_grant_latency_s_simulated": round(drain_s, 1),
            "claim_grant_note": "dominated by the 5s serving grace "
                                "the victim drains under",
            "claim_grant_control_wall_ms": round(wall_grant_ms, 1),
            "prepull_pods_during_drain": prepulls,
            "victim_restarts": victim_restarts,
            "victim_preemptions": victim_preemptions,
            "resume_bit_identical": bit_identical,
            "burst_requests": burst_requests,
            "clients": clients,
            "deadline_ms": deadline_ms,
            "burst_serving_p50_ms": round(p50 * 1e3, 2),
            "burst_serving_p99_ms": round(p99 * 1e3, 2),
            "burst_sheds_429": sheds,
            "burst_deadline_expired_504": expired,
            "burst_transport_errors": errors,
            "shed_deadline_contract_ok": contract_ok,
            **({} if on_tpu else {"cpu_compute_bound_note": True}),
        },
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model",
                    choices=["resnet", "lm", "serving", "lm-decode",
                             "lm-engine", "fleet", "data", "both"],
                    default="both",
                    help="'both' = ResNet headline (the reference's own "
                         "benchmark) with the LM suite nested in detail")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--steps-per-call", type=int, default=10,
                    help="fit host-loop fusion: k train steps per "
                         "device dispatch (1 = classic per-step loop)")
    ap.add_argument("--warmup", type=int, default=3)
    ap.add_argument("--batch", type=int, default=0,
                    help="global batch (default: per-model per-device)")
    ap.add_argument("--image-size", type=int, default=224)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--attention", default="flash",
                    help="lm attention backend: flash | dot")
    ap.add_argument("--flash-block-q", type=int, default=512,
                    help="flash attention q block (on-chip sweep knob)")
    ap.add_argument("--flash-block-k", type=int, default=1024,
                    help="flash attention k block (on-chip sweep knob; "
                         "1024 measured best on v5e @ seq 2048)")
    ap.add_argument("--flash-block-diag", type=int, default=0,
                    help="two-pass causal forward: diagonal-band fine "
                         "tile (0 = classic single pass)")
    ap.add_argument("--no-remat", action="store_true",
                    help="disable per-block remat in the lm bench")
    ap.add_argument("--moe-experts", type=int, default=0,
                    help="lm bench: replace the dense MLP with an N-expert "
                         "MoE layer (0 = dense); single-chip this measures "
                         "the dispatch/combine einsum path, multi-chip the "
                         "expert axis shards it")
    ap.add_argument("--lm-size", default="188m", choices=["188m", "470m"],
                    help="lm bench model size preset (on-TPU only)")
    ap.add_argument("--ce-chunk", type=int, default=0,
                    help="lm: sequence-chunked CE (positions per chunk; "
                         "0 = unchunked) — no [b, s, vocab] logits in "
                         "HBM, the seq-128k memory lever")
    ap.add_argument("--ce-dtype", default="f32",
                    choices=["f32", "compute"],
                    help="lm cross-entropy input precision: 'compute' "
                         "fuses f32 reductions over compute-dtype logits "
                         "(no 4-byte logits copy in HBM)")
    ap.add_argument("--quantize", default=None, choices=[None, "int8"],
                    help="lm-decode: weight-only quantization mode")
    ap.add_argument("--decode-prompt-len", type=int, default=0,
                    help="lm-decode: override prompt length (0 = model "
                         "preset); long prompts flash-prefill")
    ap.add_argument("--kv-cache", default=None, choices=[None, "int8"],
                    help="lm-decode: quantized KV cache "
                         "(per-position scales)")
    ap.add_argument("--moe-impl", default="einsum",
                    choices=["einsum", "gather"],
                    help="MoE dispatch/combine implementation "
                         "(models/moe.py; einsum measured 38.8k tok/s "
                         "at group 128 vs gather 31.0k at its best "
                         "group 256)")
    ap.add_argument("--moe-group-size", type=int, default=0,
                    help="GShard routing group (tokens) for --moe-experts; "
                         "0 = per-impl measured optimum (einsum 128, "
                         "gather 256)")
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"],
                    help="lm: optimizer (adafactor's factored second "
                         "moment cuts optimizer HBM traffic; Trainer "
                         "takes any optax tx; resnet keeps its SGD)")
    ap.add_argument("--remat-policy", default="nobatch",
                    choices=["nobatch", "dots", "minimal"],
                    help="lm remat checkpoint policy (on-chip sweep knob)")
    ap.add_argument("--no-save-attn", action="store_true",
                    help="drop flash (out, lse) residuals at the remat "
                         "boundary (recompute the fwd kernel in bwd)")
    ap.add_argument("--fake-devices", type=int, default=0,
                    help="run on an N-device virtual CPU slice")
    args = ap.parse_args()

    import os

    if args.fake_devices:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.fake_devices}"
        ).strip()
    import jax

    if args.fake_devices:
        jax.config.update("jax_platforms", "cpu")

    from kubeflow_tpu.runtime import bootstrap

    bootstrap.configure_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as e:  # jax.errors.JaxRuntimeError included
        # No backend: one parseable record on stdout AND a failing exit
        # code — a capture with no device is not a result.
        print(json.dumps({
            "metric": "backend_init_failed", "value": 0.0,
            "unit": "error", "vs_baseline": 0.0,
            "detail": {"error": f"{type(e).__name__}: {e}"}}))
        return 1
    n_chips = len(devices)
    on_tpu = devices[0].platform == "tpu"
    if args.model == "lm":
        result = bench_lm(args, devices, n_chips, on_tpu)
    elif args.model == "resnet":
        result = bench_resnet(args, devices, n_chips, on_tpu)
    elif args.model == "serving":
        result = bench_serving(args, devices, n_chips, on_tpu)
    elif args.model == "lm-decode":
        result = bench_lm_decode(args, devices, n_chips, on_tpu)
    elif args.model == "lm-engine":
        result = bench_lm_engine(args, devices, n_chips, on_tpu)
    elif args.model == "fleet":
        result = bench_fleet(args, devices, n_chips, on_tpu)
    elif args.model == "data":
        result = bench_data(args, devices, n_chips, on_tpu)
    else:
        # Soft deadline over the nested sub-benches: the one JSON line
        # prints only at the END of main, so a caller's hard timeout
        # mid-suite would record NOTHING — better to skip the tail and
        # deliver the headline.
        # Budget spent is checked between sub-benches (none is killed
        # mid-flight); KFT_BENCH_DEADLINE_S=0 disables.
        try:
            deadline_s = float(os.environ.get("KFT_BENCH_DEADLINE_S",
                                              "2700") or 0)
        except ValueError:
            # A malformed env value must not kill the capture the
            # deadline exists to protect.
            print("KFT_BENCH_DEADLINE_S unparseable; using 2700",
                  file=sys.stderr)
            deadline_s = 2700.0
        bench_t0 = time.monotonic()
        skipped: list = []

        def over_budget(name: str) -> bool:
            if deadline_s and time.monotonic() - bench_t0 > deadline_s:
                print(f"{name} sub-benchmark skipped: soft deadline "
                      f"{deadline_s:.0f}s spent", file=sys.stderr)
                skipped.append(name)
                return True
            return False

        result = bench_resnet(args, devices, n_chips, on_tpu)
        detail = result["detail"]
        failed: dict = {}

        def nested(name, bench_fn, bench_args=args, keep=None):
            """One nested sub-bench under ``detail[name]``.  A failure
            does not stop the ones after it, but it is recorded in the
            JSON and fails the exit code: a capture with a hole in it
            must not pass for a whole one."""
            if over_budget(name):
                return
            try:
                sub = bench_fn(bench_args, devices, n_chips, on_tpu)
            except Exception as e:  # noqa: BLE001 — recorded, exit != 0
                print(f"{name} sub-benchmark failed: {e}", file=sys.stderr)
                failed[name] = f"{type(e).__name__}: {e}"
                return
            detail[name] = sub["detail"] if keep is None else {
                "metric": sub["metric"], "value": sub["value"],
                "unit": sub["unit"], "vs_baseline": sub["vs_baseline"],
                **{k: sub["detail"][k] for k in keep}}

        import copy

        nested("lm", bench_lm,
               keep=("step_time_ms", "mfu", "seq_len", "attention"))
        if args.moe_experts == 0:
            # MoE MFU in the same record: 4 experts + adafactor, the
            # best configuration an on-chip sweep found.
            margs = copy.copy(args)
            margs.moe_experts = 4
            margs.optimizer = "adafactor"
            nested("lm_moe", bench_lm, margs,
                   keep=("step_time_ms", "mfu", "seq_len", "moe_experts",
                         "optimizer"))
        nested("serving", bench_serving)
        nested("lm_decode", bench_lm_decode)
        nested("lm_engine", bench_lm_engine)
        if (args.quantize, args.kv_cache) != ("int8", "int8"):
            # The quantized serving story in the same record: int8
            # weights + int8 KV cache.  Skipped when the base run was
            # already fully int8 — the numbers would be identical.
            qargs = copy.copy(args)
            qargs.quantize = "int8"
            qargs.kv_cache = "int8"
            nested("lm_decode_int8", bench_lm_decode, qargs)
        nested("data", bench_data)
        nested("hfta", bench_hfta)
        nested("colocation", bench_colocation)
        if failed:
            detail["failed_sub_benches"] = failed
        if skipped:
            detail["skipped_sub_benches"] = skipped
    emit(result)
    return 1 if result["detail"].get("failed_sub_benches") else 0


def headline_summary(result: dict,
                     full_results: str = "artifacts/bench_full.json") -> dict:
    """Compact one-line summary of a --model=both record.

    The driver keeps only the last ~2000 chars of stdout and parses the
    final line; round 4's monolithic blob exceeded that and the capture
    recorded ``parsed: null`` — the headline train numbers survived only
    in builder-run artifacts.  This pulls every north-star metric into a
    record guaranteed to fit the tail; the full blob goes to
    ``artifacts/bench_full.json`` and stderr (``emit``).
    """
    d = result.get("detail", {})

    def pick(path, key):
        node = d.get(path, {})
        return node.get(key) if isinstance(node, dict) else None

    summary = {
        "metric": result["metric"],
        "value": result["value"],
        "unit": result["unit"],
        "vs_baseline": result.get("vs_baseline"),
        "detail": {
            "device": d.get("device"),
            "resnet_images_per_sec": d.get("images_per_sec"),
            "resnet_step_ms": d.get("step_time_ms"),
            "resnet_mfu": d.get("mfu"),
            "resnet_roofline_frac":
                d.get("roofline", {}).get("frac_of_roofline"),
            "lm_tokens_per_sec_per_chip": pick("lm", "value"),
            "lm_mfu": pick("lm", "mfu"),
            "lm_seq_len": pick("lm", "seq_len"),
            "moe_tokens_per_sec_per_chip": pick("lm_moe", "value"),
            "moe_mfu": pick("lm_moe", "mfu"),
            "decode_tokens_per_sec":
                pick("lm_decode", "batched_tokens_per_sec"),
            "decode_tokens_per_sec_int8":
                pick("lm_decode_int8", "batched_tokens_per_sec"),
            "engine_tokens_per_sec":
                pick("lm_engine", "engine_tokens_per_sec"),
            "engine_vs_batcher": pick("lm_engine", "engine_vs_batcher"),
            "serving_sustained_ms_per_request":
                pick("serving", "sustained_ms_per_request"),
            "serving_batcher_capacity_req_s":
                pick("serving", "batcher_capacity_requests_per_sec"),
            "serving_small_image_req_s":
                (pick("serving", "batcher_small_image") or {}).get(
                    "requests_per_sec"),
            "data_native_examples_per_sec":
                pick("data", "pipeline_native_examples_per_sec"),
            "data_native_vs_python": pick("data", "native_vs_python_ratio"),
            "colocation_pool_utilization":
                pick("colocation", "combined_pool_utilization"),
            "colocation_burst_p99_ms":
                pick("colocation", "burst_serving_p99_ms"),
            "skipped_sub_benches": d.get("skipped_sub_benches", []),
            "failed_sub_benches": d.get("failed_sub_benches", {}),
            "full_results": full_results,
        },
    }
    summary["detail"] = {k: v for k, v in summary["detail"].items()
                         if v not in (None, [], {})}
    return summary


def shrink_detail(result: dict, limit: int = 1800,
                  full_results: str = "artifacts/bench_full.json") -> dict:
    """Fit a SINGLE-model record into the driver tail: keep as many
    detail keys as fit (smallest first — scalars survive, the big
    histograms/profiles go to the full-results file), and name what was
    dropped.  --model=both records use headline_summary instead (its
    curated cross-sub-bench names beat a greedy keep)."""
    head = {k: v for k, v in result.items() if k != "detail"}
    kept = {"full_results": full_results}
    dropped = []
    budget = limit - len(json.dumps({**head, "detail": kept})) \
        - len('"truncated_keys": ') - 40
    for k, v in sorted(result.get("detail", {}).items(),
                       key=lambda kv: len(json.dumps({kv[0]: kv[1]}))):
        cost = len(json.dumps({k: v})) + 2
        if cost <= budget:
            kept[k] = v
            budget -= cost
        else:
            dropped.append(k)
            budget -= len(json.dumps(k)) + 2
    kept["truncated_keys"] = dropped
    return {**head, "detail": kept}


def emit(result: dict) -> None:
    """Write the full record to a file + stderr; stdout gets ONE line
    that is guaranteed to fit the driver's 2000-char tail."""
    import os

    blob = json.dumps(result)
    full_results = "artifacts/bench_full.json"
    try:
        os.makedirs("artifacts", exist_ok=True)
        with open(full_results, "w") as f:
            f.write(blob + "\n")
    except OSError as e:  # read-only cwd must not kill the capture
        print(f"bench_full.json not written: {e}", file=sys.stderr)
        # Don't advertise an artifact that doesn't exist — the only
        # full copy is then the stderr line below.
        full_results = "stderr (FULL RESULT line)"
    print(f"FULL RESULT: {blob}", file=sys.stderr)
    if len(blob) <= 1800:
        print(blob)
    elif any(k in result.get("detail", {}) for k in
             ("lm", "lm_moe", "serving", "lm_decode", "lm_engine",
              "data")):
        print(json.dumps(headline_summary(result, full_results)))
    else:
        print(json.dumps(shrink_detail(result, full_results=full_results)))


if __name__ == "__main__":
    sys.exit(main())
