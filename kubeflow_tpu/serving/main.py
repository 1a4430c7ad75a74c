"""Serving container entrypoint.

Flag-compatible heir of the model server invocation the reference's
manifests assembled: ``tensorflow_model_server --port=9000
--model_name=... --model_base_path=...``
(kubeflow/tf-serving/tf-serving.libsonnet:118-132) plus the http proxy's
``--port 8000`` sidecar (:176-207) — here one process serves both wire
protocols over one set of warm models on the local TPU: the gRPC
PredictionService on ``--grpc_port`` (:9000, the reference's primary
protocol) and the REST contract on ``--port`` (:8000).
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading
import time

from kubeflow_tpu.serving.engine import PREFILL_CHUNK_TOKENS
from kubeflow_tpu.serving.http import make_http_server
from kubeflow_tpu.serving.model_server import ModelServer
from kubeflow_tpu.testing import faults


def batcher_factory(*, micro_batch_size: int, batch_timeout_s: float,
                    lm_buckets: str = "",
                    lm_max_promotion_factor: float = 4.0,
                    lm_engine: bool = True,
                    lm_engine_slots: int = 8,
                    lm_engine_prefill_len: int = 0,
                    lm_engine_admit_width: int = 4,
                    decode_rounds: int = 8,
                    prefill_chunk_tokens: int = PREFILL_CHUNK_TOKENS,
                    kv_block_tokens: int = 16,
                    kv_pool_blocks: int = 0,
                    host_spill_blocks: int = 0,
                    prefix_caching: bool = True,
                    max_queue_depth: int = 0,
                    overload_retry_after_s: float = 1.0,
                    adapters_dir: str = "",
                    adapter_slots: int = 8,
                    adapter_rank: int = 4,
                    mesh: str = ""):
    """ModelServer.enable_batching factory: picks the batcher per model.

    lm_generate models default to the continuous-batching DecodeEngine
    (serving/engine.py: persistent slot cache, in-flight admission,
    immediate retirement); ``lm_engine=False`` (--lm_static_batcher)
    falls back to the static left-padding BucketedLMBatcher when
    buckets are configured.  Everything else gets the shape-grouped
    MicroBatcher when micro-batching is on, or no batcher at all
    (build returns None -> direct predict path).  Rebuilt around every
    hot-swapped version by ModelServer.
    """
    from kubeflow_tpu.serving import sharding
    from kubeflow_tpu.serving.engine import DecodeEngine
    from kubeflow_tpu.serving.model_server import (
        BucketedLMBatcher,
        MicroBatcher,
    )

    sizes = [s for s in (1, 2, 4, 8, 16, 32, 64, 128)
             if s <= micro_batch_size]
    if not sizes or sizes[-1] != micro_batch_size:
        sizes.append(micro_batch_size)
    buckets = [int(b) for b in lm_buckets.split(",") if b.strip()]
    # Parsed once (fail fast on a typo'd --mesh), built per engine:
    # the mesh object itself is cheap, and a rebuilt engine after
    # hot-swap must re-place its params on the same devices anyway.
    mesh_axes = sharding.parse_mesh_flag(mesh)

    def build(model):
        spec = getattr(model.predict, "engine_spec", None)
        if lm_engine and spec is not None:
            # Prefill width: explicit flag > largest bucket > a capped
            # share of whatever prompt room the model's max_seq_len
            # leaves after the configured completion budget.  The width
            # is a STATIC program shape (the four-program guarantee), so
            # every admission prefills at this width no matter how
            # short the prompt, and the persistent cache is sized
            # slots x (width + budget) — hence the flagless cap: a
            # 2048-ctx model must not pay near-full-context prefill
            # per admission by default.  Prompts beyond the width fall
            # back to the direct generate() path (exactly the old
            # flagless behavior), and everything is clamped to the
            # model's real prompt room so a config that fit the static
            # batchers never turns into a construction crash here; if
            # no room is left at all, fall through to the static paths.
            cap = (spec["cfg"].max_seq_len
                   - spec["decode"].max_new_tokens)
            prefill = lm_engine_prefill_len or (
                max(buckets) if buckets else min(cap, 512))
            prefill = min(prefill, cap)
            if prefill >= 1:
                registry = None
                if adapters_dir:
                    # Multi-model adapter serving (§5.11): one registry
                    # per engine; hot-loaded per-tenant deltas ride the
                    # stacked adapter array inside the SAME programs.
                    from kubeflow_tpu.serving.adapters import (
                        AdapterRegistry,
                    )

                    registry = AdapterRegistry(
                        spec["cfg"], slots=adapter_slots,
                        rank=adapter_rank, directory=adapters_dir,
                        name=f"{model.name}-v{model.version}",
                        overload_retry_after_s=overload_retry_after_s)
                logging.info(
                    "decode engine for %r v%d: %d slots, prefill width "
                    "%d, cache %d cols/slot", model.name, model.version,
                    lm_engine_slots, prefill,
                    prefill + spec["decode"].max_new_tokens)
                return DecodeEngine(
                    spec["cfg"], spec["params"], spec["decode"],
                    slots=lm_engine_slots, prefill_len=prefill,
                    decode_rounds=decode_rounds,
                    admit_width=lm_engine_admit_width,
                    prefill_chunk_tokens=prefill_chunk_tokens,
                    kv_block_tokens=kv_block_tokens,
                    kv_pool_blocks=kv_pool_blocks,
                    host_spill_blocks=host_spill_blocks,
                    prefix_caching=prefix_caching,
                    max_queue_depth=max_queue_depth,
                    overload_retry_after_s=overload_retry_after_s,
                    adapters=registry,
                    mesh=sharding.build_mesh(mesh_axes),
                    name=f"{model.name}-v{model.version}")
            logging.warning(
                "decode engine disabled for %r: max_new_tokens %d "
                "leaves no prompt room in max_seq_len %d", model.name,
                spec["decode"].max_new_tokens, spec["cfg"].max_seq_len)
        if micro_batch_size <= 0:
            return None  # direct predict path
        kwargs = dict(
            max_batch_size=micro_batch_size,
            batch_timeout_s=batch_timeout_s,
            allowed_batch_sizes=sizes,
            max_queue_depth=max_queue_depth,
            overload_retry_after_s=overload_retry_after_s,
            name=f"{model.name}-v{model.version}",
        )
        loader = str(model.meta.get("loader", ""))
        if buckets and loader.endswith("lm_generate"):
            return BucketedLMBatcher(
                model.predict, buckets=buckets,
                max_promotion_factor=(lm_max_promotion_factor
                                      if lm_max_promotion_factor > 0
                                      else None),
                **kwargs)
        return MicroBatcher(model.predict, **kwargs)

    return build


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="kubeflow-tpu-serve")
    ap.add_argument("--model_name", required=True)
    ap.add_argument("--model_base_path", required=True)
    ap.add_argument("--port", type=int, default=8000,
                    help="REST port (reference http-proxy contract)")
    ap.add_argument("--grpc_port", type=int, default=9000,
                    help="gRPC PredictionService port (reference "
                         "tensorflow_model_server contract); -1 disables")
    ap.add_argument("--poll_interval_s", type=float, default=2.0,
                    help="model version poll period (hot-swap latency)")
    ap.add_argument("--host", default="0.0.0.0")
    ap.add_argument("--micro_batch_size", type=int, default=0,
                    help="coalesce concurrent single-row requests into "
                         "device batches up to this size (0 = off) — "
                         "the TF-Serving batching-parameters idea, "
                         "TPU-shaped; survives hot-swap")
    ap.add_argument("--batch_timeout_ms", type=float, default=5.0,
                    help="micro-batch assembly window per shape group")
    ap.add_argument("--lm_buckets", default="",
                    help="comma-separated prompt-length buckets; with "
                         "--micro_batch_size on an lm_generate model, "
                         "mixed-length prompts left-pad to these so "
                         "they share batched decode programs")
    ap.add_argument("--lm_max_promotion_factor", type=float, default=4.0,
                    help="bound on dispatch-time bucket promotion: only "
                         "prompts whose buckets are within this factor "
                         "share a batch (a short prompt then never pays "
                         "more than factor x its own bucket's KV span "
                         "per decode step); <=0 = unbounded, one "
                         "shared queue")
    ap.add_argument("--lm_static_batcher", action="store_true",
                    help="serve lm_generate models through the static "
                         "BucketedLMBatcher (pad-at-dispatch whole-"
                         "generation programs) instead of the default "
                         "continuous-batching DecodeEngine")
    ap.add_argument("--lm_engine_slots", type=int, default=8,
                    help="DecodeEngine concurrent sequences (persistent "
                         "KV-cache rows)")
    ap.add_argument("--lm_engine_prefill_len", type=int, default=0,
                    help="DecodeEngine static prompt width (0 = largest "
                         "--lm_buckets entry, else max_seq_len minus "
                         "max_new_tokens capped at 512; always clamped "
                         "to the model's prompt room); longer prompts "
                         "fall back to the direct generate() path.  "
                         "Every admission prefills at this width and "
                         "the persistent KV cache is sized by it — set "
                         "it near your real prompt lengths on long-"
                         "context models")
    ap.add_argument("--decode_rounds", type=int, default=8,
                    help="DecodeEngine decode rounds: up to k "
                         "steps run device-resident per dispatch in a "
                         "while_loop with early exit when every slot "
                         "finishes, host uploads double-buffered "
                         "behind device compute (docs §5.2e).  The "
                         "width adapts between 1 and k on early-exit "
                         "waste and queued admissions, and is clamped "
                         "under the tightest live deadline")
    ap.add_argument("--lm_engine_admit_width", type=int, default=4,
                    help="DecodeEngine concurrent mid-prefill "
                         "admissions: further queued requests wait "
                         "even when slots are free, so a burst of long "
                         "prompts cannot hoard every slot half-filled")
    ap.add_argument("--prefill_chunk_tokens", type=int,
                    default=PREFILL_CHUNK_TOKENS,
                    help="DecodeEngine prefill chunk width in prompt "
                         "tokens (clamped to the prefill width).  The "
                         "default, 256, is the chip's ridge: a call "
                         "reads every weight once, and a bf16 weight "
                         "gives one FLOP a byte a token, so under "
                         "197e12 / 819e9 = ~240 tokens (TPU v5e) the "
                         "read bounds the call; rounded up to the "
                         "128-row tile.  At most one chunk runs "
                         "between two decode rounds (while requests "
                         "decode, once the rounds have saved up its "
                         "width at 64 prompt tokens a round), so an "
                         "in-flight request's longest inter-token gap "
                         "is one round plus one chunk regardless of "
                         "prompt length")
    ap.add_argument("--kv_block_tokens", type=int, default=16,
                    help="DecodeEngine paged-KV page size in cache "
                         "positions — also the prefix hash/share "
                         "granularity (shared prefixes alias in "
                         "multiples of this many tokens)")
    ap.add_argument("--kv_pool_blocks", type=int, default=0,
                    help="DecodeEngine device KV block-pool capacity "
                         "in pages (0 = slots x ceil(max_len / "
                         "kv_block_tokens), capacity parity with a "
                         "slot-reserved cache).  Serving capacity is "
                         "bounded by TOKENS RESIDENT in this pool, not "
                         "slot count: mixed-length traffic fits far "
                         "more requests than the worst case, and "
                         "exhaustion sheds typed Overloaded (429)")
    ap.add_argument("--host_spill_blocks", type=int, default=0,
                    help="DecodeEngine host-RAM KV spill tier capacity "
                         "in pages (0 = disabled, §5.10).  LRU-cold "
                         "prefix records and parked multi-turn "
                         "sessions evacuate to host memory under pool "
                         "pressure and re-import through kv_import on "
                         "the next hit — tokens-addressable capacity "
                         "becomes (kv_pool_blocks + host_spill_blocks)"
                         " x kv_block_tokens, and the :fetch_kv route "
                         "serves these pages to failover peers")
    ap.add_argument("--no_prefix_cache", action="store_true",
                    help="disable shared-prefix block aliasing "
                         "(admissions never resume from cached "
                         "prefixes; the paged pool and chunked "
                         "prefill still apply)")
    ap.add_argument("--adapters_dir", default="",
                    help="directory of per-tenant adapter deltas "
                         "(<name>.npz + digest sidecar, §5.11): enables "
                         "multi-model serving on the DecodeEngine — "
                         "requests naming 'model@adapter' hot-load the "
                         "delta into a bounded stacked-array slot and "
                         "co-batch with every other variant in the SAME "
                         "compiled programs.  Empty = adapter requests "
                         "404")
    ap.add_argument("--adapter_slots", type=int, default=8,
                    help="resident adapter variants per engine (the "
                         "stacked array's device rows beyond base); "
                         "idle adapters LRU-evict when the slots fill, "
                         "in-flight ones are pinned — all slots pinned "
                         "sheds 429")
    ap.add_argument("--adapter_rank", type=int, default=4,
                    help="low-rank adapter factor rank: every adapter "
                         "served by one engine shares this rank (the "
                         "stacked array is one static shape)")
    ap.add_argument("--mesh", default="",
                    help="serving mesh spec, e.g. 'tensor=4': shard "
                         "the DecodeEngine's params and paged KV pool "
                         "over that many local devices (regex "
                         "partition rules, serving/sharding.py) so "
                         "one model spans a pod slice.  Empty = "
                         "single-device.  On CPU, simulate chips "
                         "with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N")
    ap.add_argument("--role", default="unified",
                    choices=("unified", "prefill", "decode"),
                    help="disaggregated-serving tier, advertised on "
                         "/readyz: 'prefill' replicas serve :prefill "
                         "(chunked prefill into KV handoff pages), "
                         "'decode' replicas import handoffs and "
                         "stream; the fleet router pipelines "
                         ":generate across the two pools.  'unified' "
                         "(default) keeps the single-tier path")
    ap.add_argument("--max_queue_depth", type=int, default=256,
                    help="bounded admission: submissions beyond this "
                         "many pending requests per model fail fast "
                         "with HTTP 429 / gRPC RESOURCE_EXHAUSTED "
                         "instead of queueing unboundedly (0 = "
                         "unbounded)")
    ap.add_argument("--max_inflight", type=int, default=512,
                    help="per-model in-flight request cap across ALL "
                         "paths — including the direct (un-batched) "
                         "one, which has no queue to bound it; beyond "
                         "it submissions shed with 429 (0 = unbounded)")
    ap.add_argument("--overload_retry_after_s", type=float, default=1.0,
                    help="Retry-After hint carried by shed (429) "
                         "responses")
    ap.add_argument("--dedup_capacity", type=int, default=1024,
                    help="idempotency dedup cache entries (completed "
                         "results answered to retried keys; in-flight "
                         "duplicates attach instead of re-executing)")
    ap.add_argument("--dedup_ttl_s", type=float, default=120.0,
                    help="how long a completed idempotency-key result "
                         "stays answerable (policy clock); 0 disables "
                         "expiry")
    ap.add_argument("--drain_deadline_s", type=float, default=30.0,
                    help="graceful-drain budget on SIGTERM: /readyz "
                         "flips not-ready immediately, then in-flight "
                         "requests get this long to finish before the "
                         "listeners close (match it to the pod's "
                         "terminationGracePeriodSeconds)")
    ap.add_argument("--reload_backoff_s", type=float, default=0.5,
                    help="initial circuit-breaker backoff after a "
                         "model (re)load failure (doubles per failure, "
                         "jittered; the last-good version keeps "
                         "serving while the breaker is open)")
    ap.add_argument("--reload_backoff_cap_s", type=float, default=60.0,
                    help="circuit-breaker backoff ceiling")
    from kubeflow_tpu.runtime import tracing

    tracing.add_cli_args(ap)
    args = ap.parse_args(argv)

    logging.basicConfig(level=logging.INFO, stream=sys.stderr)
    from kubeflow_tpu.runtime import bootstrap

    bootstrap.configure_compile_cache()
    bootstrap.report_devices()
    if tracing.enable_from_args(args) is not None:
        logging.info("request tracing on (sample rate %g, store %d "
                     "traces) — GET /debug/traces",
                     args.trace_sample_rate, args.trace_capacity)
    # Scripted chaos (KFT_FAULTS env var): no-op unless set — see
    # kubeflow_tpu/testing/faults.py for the grammar.
    if faults.install_from_env() is not None:
        logging.warning("fault injection ACTIVE (KFT_FAULTS set)")
    server = ModelServer(
        poll_interval_s=args.poll_interval_s,
        reload_backoff_s=args.reload_backoff_s,
        reload_backoff_cap_s=args.reload_backoff_cap_s,
        max_inflight=args.max_inflight,
        overload_retry_after_s=args.overload_retry_after_s,
        dedup_capacity=args.dedup_capacity,
        dedup_ttl_s=args.dedup_ttl_s,
        role=args.role)
    server.add_model(args.model_name, args.model_base_path)
    # The factory is installed whenever ANY batching path might apply:
    # lm_generate models default to the continuous DecodeEngine even
    # with micro-batching off (it is the serving hot path, not an
    # opt-in); --lm_static_batcher restores the old behavior.
    if args.micro_batch_size > 0 or not args.lm_static_batcher:
        server.enable_batching(
            args.model_name,
            batcher_factory(
                micro_batch_size=args.micro_batch_size,
                batch_timeout_s=args.batch_timeout_ms / 1e3,
                lm_buckets=args.lm_buckets,
                lm_max_promotion_factor=args.lm_max_promotion_factor,
                lm_engine=not args.lm_static_batcher,
                lm_engine_slots=args.lm_engine_slots,
                lm_engine_prefill_len=args.lm_engine_prefill_len,
                lm_engine_admit_width=args.lm_engine_admit_width,
                decode_rounds=args.decode_rounds,
                prefill_chunk_tokens=args.prefill_chunk_tokens,
                kv_block_tokens=args.kv_block_tokens,
                kv_pool_blocks=args.kv_pool_blocks,
                host_spill_blocks=args.host_spill_blocks,
                prefix_caching=not args.no_prefix_cache,
                max_queue_depth=args.max_queue_depth,
                overload_retry_after_s=args.overload_retry_after_s,
                adapters_dir=args.adapters_dir,
                adapter_slots=args.adapter_slots,
                adapter_rank=args.adapter_rank,
                mesh=args.mesh,
            ),
        )
        logging.info(
            "request batching on: %s%s",
            ("continuous decode engine (slots=%d)"
             % args.lm_engine_slots if not args.lm_static_batcher
             else "static batchers"),
            (", micro batch size<=%d, window %.1f ms"
             % (args.micro_batch_size, args.batch_timeout_ms)
             if args.micro_batch_size > 0 else ""))
    server.start_watcher()
    httpd, _ = make_http_server(server, port=args.port, host=args.host)
    grpc_server = None
    if args.grpc_port >= 0:
        # Deferred import: grpcio is the [serving] extra; a REST-only
        # deployment (--grpc_port -1) must run without it installed.
        from kubeflow_tpu.serving.grpc_server import make_grpc_server

        grpc_server = make_grpc_server(server, port=args.grpc_port,
                                       host=args.host)
        logging.info("serving %r on rest=:%d grpc=:%d", args.model_name,
                     args.port, grpc_server.bound_port)
    else:
        logging.info("serving %r on rest=:%d (grpc disabled)",
                     args.model_name, args.port)
    # Readiness marker for process-spawning tests/orchestration: the
    # bound ports, on one parseable stderr line, after both servers are up.
    print(f"KFT_SERVING_READY rest={httpd.server_address[1]} "
          f"grpc={grpc_server.bound_port if grpc_server else -1}",
          file=sys.stderr, flush=True)

    stop = threading.Event()

    def on_signal(*_):
        # Readiness flips INSIDE the handler: the load balancer must
        # see /readyz go 503 at the first possible instant, while
        # /healthz stays 200 (a draining pod is alive, not dead).
        server.begin_drain()
        stop.set()

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    stop.wait()
    # Graceful drain: requests already accepted — and stragglers routed
    # here before the endpoint controller catches up — finish inside
    # the drain budget; only then do the listeners close.  Rolling
    # updates on GKE therefore lose zero accepted requests (the engine
    # additionally drains its in-flight slots in server.stop()).
    drained = wait_for_drain(server, args.drain_deadline_s)
    logging.info("drain %s after SIGTERM (in-flight now %d)",
                 "complete" if drained else "deadline exceeded",
                 server.inflight())
    httpd.shutdown()
    if grpc_server is not None:
        grpc_server.stop(grace=1)
    server.stop()
    bootstrap.report_memory()
    return 0


def wait_for_drain(server: ModelServer, deadline_s: float,
                   settle_s: float = 0.25,
                   poll_s: float = 0.02) -> bool:
    """Block until the server's in-flight count stays at zero for
    ``settle_s`` (new stragglers may still arrive while load balancers
    catch up with the readiness flip) or ``deadline_s`` passes.
    Returns True when the server quiesced inside the budget."""
    deadline = faults.monotonic() + max(0.0, deadline_s)
    quiet_since = None
    while faults.monotonic() < deadline:
        if server.inflight() == 0:
            if quiet_since is None:
                quiet_since = faults.monotonic()
            elif faults.monotonic() - quiet_since >= settle_s:
                return True
        else:
            quiet_since = None
        time.sleep(poll_s)
    return server.inflight() == 0


if __name__ == "__main__":
    sys.exit(main())
