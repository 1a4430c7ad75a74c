"""Continuous-batching LM decode engine: slot-based serving loop.

The static batchers (MicroBatcher / BucketedLMBatcher) dispatch whole
``generate()`` programs: a batch is assembled, padded, and OWNED by one
device program from prefill to the last token.  Two structural costs
follow — a request that arrives mid-generation waits for the entire
program, and every row pays the batch bucket's padded KV span on every
decode step (models/generate.py's docstring measures ~6x wasted decode
compute on wide length distributions).

This engine runs the slot entry points instead (models/generate.py)
over ONE persistent PAGED KV block pool shared by ``slots`` sequences:

  - the unified KV store is a device-side block pool
    ([planes, kv_pool_blocks, kv_block_tokens, hkv, d], one plane per
    (loop step, layer): cfg.kv_planes; fp and int8
    QTensor alike) with host-owned per-slot block tables passed into
    every program call — a slot holds pages for the tokens it has
    actually produced, so serving capacity is bounded by **tokens
    resident** (free blocks), not slots x max_len, and admission
    sheds typed ``Overloaded`` when the pool is exhausted instead of
    deadlocking (each admission reserves its worst-case page count up
    front; see serving/prefix_cache.py BlockManager);
  - a dedicated loop advances all live slots in ROUNDS: one
    ``decode_rounds`` call runs up to ``decode_rounds`` steps on the
    device (the width adapts under that cap, see ``_round_width``) and
    stops early once every slot is done;
  - new requests are admitted into free slots BETWEEN rounds, and their
    prompts prefill in **static-width chunks** (``prefill_chunk_tokens``
    columns, ``PREFILL_CHUNK_TOKENS`` = 256 by default, clamped to
    ``prefill_len``): an admission's first chunk at its claim, then at
    most ONE chunk of the oldest admission between two decode rounds,
    once the rounds since the last have saved up its width at
    ``PREFILL_ROUND_TOKENS`` = 64 a round (every round with nothing
    live) — a long arriving prompt holds a live slot's next token back
    by one chunk's compute every 4th round (its longest gap is one
    round plus one chunk), where a one-shot full-width prefill stalls
    every active slot for the whole prompt;
  - admission first resumes from the **longest cached shared prefix**:
    the block-hashed index finds the longest token-block prefix a
    previous prompt already computed and the new slot's table ALIASES
    those physical blocks (a refcount bump — zero device copies;
    divergence lands in a fresh private block because sharing is
    block-aligned, i.e. copy-on-write whose copy is statically dead),
    and chunked prefill continues after them — TTFT scales with the
    *uncached suffix* length, not the full prompt (the win for fleets
    of chat requests sharing a system prompt);
  - finished rows retire immediately (device-side ``done`` flag),
    their slots are reused and their private pages return to the pool
    (published prefix pages stay resident until LRU eviction) — no
    request ever waits for the batch to drain, and per-request
    ``max_new_tokens`` is data, not a compiled constant;
  - the only speculation is the one a model brings: where its
    multi-token-prediction module drafts (``mtp_layers``), every step
    of a decode round verifies a draft made on the device and may
    yield two tokens (models/generate.py ``_advance_slots_drafting``);
  - every shape is static, so the engine's whole lifetime compiles at
    most THREE programs (chunked prefill, decode rounds and, on a
    decode-tier engine that imports disaggregated KV handoffs,
    ``kv_import``, run once per imported request; prefix reuse needs
    no copy program at all);
  - with ``mesh`` set (serving/sharding.py) the SAME programs compile
    tensor-parallel: params and the block pool are placed with
    NamedShardings at construction (heads / MLP hidden / vocab split,
    the pool on its kv-head dim) and XLA partitions every program
    from the argument shardings — host-owned block tables, admission,
    and the step loop are untouched, and greedy tokens are identical
    to the single-device engine;
  - disaggregated serving rides the same block pool: a prefill-tier
    request (``kv_export``) returns its finished full-block pages as
    a handoff payload, and a decode-tier admission (``kv_handoff``)
    scatters transferred pages into reserved blocks and resumes
    through the ordinary cached-prefix chunked-prefill path.

The host's work for the NEXT round (page covers, the block-table
upload, spill) runs while the device computes the current one, and the
round's tokens are read once.  Where another decode round
follows, the loop keeps ONE round in flight: it dispatches round N+1
(and the chunk before it) before it reads round N, so the read, the
drain to the clients, the accounting and the next admission run beside
a round on the device, not after one (``_dispatch_round``; the depth
is the design, not a dial; a stopping engine reads first).  Completion
is detected deterministically from the per-request budget (and, when
EOS is configured, from the round's tokens — the device flag has
already frozen the slot by then).

Interface-compatible with the batchers (submit/accepts/stats/close), so
ModelServer.enable_batching wires it behind the REST and gRPC surfaces
unchanged.
"""

from __future__ import annotations

import bisect
import logging
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from kubeflow_tpu.runtime import tracing
from kubeflow_tpu.serving.errors import (
    BatcherClosed,
    DeadlineExceeded,
    Overloaded,
)
from kubeflow_tpu.serving.model_server import (
    EXPIRED_HELP,
    EXPIRED_TOTAL,
    SHED_HELP,
    SHED_TOTAL,
    locked_snapshot,
)
from kubeflow_tpu.serving.adapters import AdapterNotFound
from kubeflow_tpu.serving.prefix_cache import BlockManager
from kubeflow_tpu.testing import faults

log = logging.getLogger(__name__)


# The chunk program's width where nobody states one.  A call reads every
# weight once for all of its columns, and a bf16 weight gives one FLOP a
# byte a column, so the read bounds the call until the columns reach the
# chip's ridge: peak bf16 FLOP/s over HBM bytes/s, 197e12 / 819e9 = ~240
# on a TPU v5e.  Rounded up to a multiple of the MXU's 128-row tile: 256
# (at 64 the prompt paid the read four times over).  A property of the
# chip and of the weights' width, not of a model.
PREFILL_CHUNK_TOKENS = 256

# The prompt tokens a decode round's live slots wait for: what one chunk
# between two rounds held until the chunk grew wider than this (the value
# dates from PR 4).  A wider chunk runs once the rounds since the last
# have saved up its width — every 4th round at 256 columns — so prefill
# keeps the tokens a round it had and costs the live slots a quarter of a
# 23 ms chunk where it cost a whole 15 ms one; with no slot live nothing
# is held back.  This is the dial of the prefill / decode trade: at one
# 256-wide chunk EVERY round docqa's closed loop served 82 % more tokens a
# second at 57 % less time to first token and 8 % MORE time per output
# token, the one judged number (PERF.md section 6, PR 36; section 7 row
# 10).
PREFILL_ROUND_TOKENS = 64


class _SpillShed(Exception):
    """Internal: a spill-tier fault struck mid-admission (the
    engine.spill site raised during re-import).  The admission
    dispatcher catches this and sheds the one affected request typed
    429 — never engine death, never a leaked page."""


# Step-duration histogram buckets: decode steps run ~0.1 ms (tiny CPU
# smoke models) to ~100 ms (big models, wide fused rounds).
_STEP_BUCKETS = (.0005, .001, .0025, .005, .01, .025, .05, .1, .25, .5,
                 1.0, 2.5)

PREFIX_HITS_TOTAL = "kft_engine_prefix_hits_total"
PREFIX_HITS_HELP = "admissions resumed from a cached prefix, by engine"
PREFIX_MISSES_TOTAL = "kft_engine_prefix_misses_total"
PREFIX_MISSES_HELP = "admissions with no cached prefix, by engine"
PREFIX_EVICTIONS_TOTAL = "kft_engine_prefix_evictions_total"
PREFIX_EVICTIONS_HELP = "cached prefix records evicted (LRU), by engine"
KV_BLOCKS_GAUGE = "kft_engine_kv_blocks"
KV_BLOCKS_HELP = "paged KV pool capacity in blocks, by engine"
KV_BLOCKS_USED_GAUGE = "kft_engine_kv_blocks_used"
KV_BLOCKS_USED_HELP = \
    "paged KV blocks resident (slot- or cache-held), by engine"
KV_EVICTIONS_TOTAL = "kft_engine_kv_block_evictions_total"
KV_EVICTIONS_HELP = \
    "paged KV blocks freed by prefix-cache LRU eviction, by engine"
KV_SHED_TOTAL = "kft_engine_kv_shed_no_blocks_total"
KV_SHED_HELP = \
    "submissions shed because the KV block pool could not cover " \
    "them, by engine"
PREFILL_CHUNKS_TOTAL = "kft_engine_prefill_chunks_total"
PREFILL_CHUNKS_HELP = "prefill chunk program calls, by engine"
MTP_DRAFTED_TOTAL = "kft_engine_mtp_drafted_total"
MTP_DRAFTED_HELP = \
    "drafts of a model's own multi-token-prediction module verified by " \
    "a decode step, by engine"
MTP_ACCEPTED_TOTAL = "kft_engine_mtp_accepted_total"
MTP_ACCEPTED_HELP = \
    "drafts of a model's own multi-token-prediction module taken (the " \
    "step yielded two tokens), by engine"
MESH_DEVICES_GAUGE = "kft_engine_mesh_devices"
MESH_DEVICES_HELP = \
    "devices the engine's serving mesh spans (1 = single-device), " \
    "by engine"
HANDOFF_PAGES_TOTAL = "kft_engine_handoff_pages_total"
HANDOFF_PAGES_HELP = \
    "paged-KV pages transferred for disaggregated prefill/decode " \
    "handoff, by engine and direction (export/import)"
FUSED_ROUNDS_TOTAL = "kft_engine_fused_rounds_total"
FUSED_ROUNDS_HELP = \
    "decode rounds dispatched, by engine"
FUSED_WASTED_TOTAL = "kft_engine_fused_steps_wasted_total"
FUSED_WASTED_HELP = \
    "fused-round slot-steps dispatched but not delivered (early-exit " \
    "waste past a slot's EOS/budget/deadline), by engine"
SPARSE_READS_TOTAL = "kft_engine_sparse_positions_total"
SPARSE_READS_HELP = \
    "positions the decode steps of a stack with an indexer or sliding " \
    "layers read, by engine and kind (index_scored, index_chosen, " \
    "window_read; index_read: the index keys the program read to " \
    "score those), summed over slots, steps and planes"
DECODE_KERNEL_STEPS_TOTAL = "kft_engine_decode_kernel_steps_total"
DECODE_KERNEL_STEPS_HELP = \
    "decode steps run by a step program that holds the paged " \
    "attention kernel (ops/paged_attention.py), by engine; over " \
    "steps: the share the kernel served"
KV_SPILLED_GAUGE = "kft_engine_kv_spilled_blocks"
KV_SPILLED_HELP = \
    "paged-KV pages currently resident in the host spill tier, " \
    "by engine"
HOST_TIER_GAUGE = "kft_engine_host_tier_blocks"
HOST_TIER_HELP = \
    "host spill-tier capacity in pages (0 = tier disabled), by engine"
KV_SPILL_TOTAL = "kft_engine_kv_spill_total"
KV_SPILL_HELP = \
    "paged-KV pages crossing the host spill tier, by engine and " \
    "direction (out = device pages evacuated to host, in = host " \
    "pages re-imported at admission)"
LOOP_SECONDS_TOTAL = "kft_engine_loop_seconds_total"
LOOP_SECONDS_HELP = \
    "wall seconds of the engine's loop thread, by engine and phase " \
    "of an iteration (the phases tile it; round_wait is the host " \
    "blocked on the device, round_read the round's other reads " \
    "once its first result has landed)"
QUEUE_WAIT_TOTAL = "kft_engine_queue_wait_seconds_total"
QUEUE_WAIT_HELP = \
    "seconds admitted requests waited from submit to slot claim, " \
    "summed, by engine"
PREFILL_SPAN_TOTAL = "kft_engine_prefill_span_seconds_total"
PREFILL_SPAN_HELP = \
    "seconds from slot claim to a request's first token, summed, " \
    "by engine"
COMPILE_SECONDS_TOTAL = "kft_engine_compile_seconds_total"
COMPILE_SECONDS_HELP = \
    "wall seconds spent lowering and compiling the engine's AOT " \
    "programs, by engine"
ADAPTER_REQUESTS_TOTAL = "kft_engine_adapter_requests_total"
ADAPTER_REQUESTS_HELP = \
    "requests admitted naming an adapter variant, by engine and " \
    "adapter"

# Decode rounds: shrink the adaptive round
# width when more than this fraction of a round's dispatched slot-steps
# delivered nothing (early-exit waste: slots frozen at EOS/budget while
# co-resident slots keep stepping), or when an admission is queued
# (smaller rounds reach the admission boundary sooner); grow back one
# step per full, waste-free round — the PR 7 adaptive-width discipline
# applied to the round dimension.  The pace EMA smooths the per-token
# step latency used to clamp the width under live deadlines.
_ROUND_WASTE_FRAC = 0.25
_ROUND_PACE_ALPHA = 0.2


# The phases that tile one iteration of DecodeEngine._run (see _Phase).
_PHASES = ("wait_work", "admit", "housekeeping", "prefill_dispatch",
           "round_prepare", "round_dispatch", "overlap", "round_wait",
           "round_read", "drain", "account")
_PHASE_KEY = {p: f"loop_{p}_s" for p in _PHASES}
_PHASE_NOTE = {p: f"kft.engine.{p}" for p in _PHASES}
# Cumulative counters that stats() hands out under their own names (a
# window is two readings subtracted).  Where the loop thread's wall time
# went, by phase (_Phase), and its iterations; per request, submit ->
# slot claim over the requests admitted, slot claim -> first token over
# the first tokens delivered, and the latter again over the requests
# that resumed a cached prefix; wall seconds of every AOT compile and
# the largest program by the compiler's own account (arguments + outputs
# + temporaries - aliased: memory_stats() leaves temporaries out).
# The turnaround between two rounds (_ReadPhase, _device_has_work): from
# the first result of a round that the loop did not get ahead of (nothing
# was queued behind it) to the return of the next call that hands the
# device work, summed and counted.  The loop thread's CPU
# seconds (time.thread_time(), stored once an iteration): over the
# phases in which it is not blocked (all but wait_work and round_wait)
# they say whether its work ran or waited for a core or the interpreter
# lock.  The iterations that took _SLOW_ROUND_FACTOR times the running
# mean, and the seconds they took over it (_note_iteration).  The decode
# rounds dispatched while the round before them was unread
# (``_dispatch_round``): over the rounds, how often the loop kept the
# device one round ahead of itself.
_SUM_KEYS = ("loop_rounds", "rounds_ahead", *_PHASE_KEY.values(),
             "turnaround_s_sum", "turnarounds", "loop_cpu_s",
             "slow_rounds", "slow_round_s_sum",
             "queue_wait_s_sum", "admitted",
             "prefill_span_s_sum", "first_tokens",
             "prefill_span_hit_s_sum", "first_tokens_hit",
             "compile_s", "compiled_peak_bytes")
# The decode steps' (row, choice) pairs by where they fell, in the order
# of ``state["moe_pairs"]`` (models/generate.py init_paged_state).
_PAIR_KEYS = ("pairs_held", "pairs_zero", "pairs_absent")
# What the decode steps of a stack with an indexer or sliding layers read,
# by (slot, step, plane): index keys scored, positions chosen of them,
# window positions read, and the index keys the program READ to score
# the first (``DecodeEngine._sparse_reads``).
_SPARSE_KEYS = ("index_scored", "index_chosen", "window_read",
                "index_read")
# What ``decode_rounds`` counts of a call into the state it is handed
# (it starts them at zero): the host reads them a round late, after the
# state has been donated on, so each round's are taken out of the state
# at its dispatch and spare arrays ride on in their place
# (``DecodeEngine._dispatch_round``).
_COUNT_KEYS = ("moe_touched", "moe_pairs", "mtp_counts")
# An iteration that takes this many times the running mean of the
# wall time (wait_work left out) of the iterations that dispatched a
# round or waited for the device is counted and logged with its own
# phase times; the mean is the plain one of its first _ITER_MEAN_SAMPLES
# iterations (the first of all waits for a program's first run) and
# follows at one in so many from there, a slow one counted as the limit
# it passed.
_SLOW_ROUND_FACTOR = 8
_ITER_MEAN_SAMPLES = 64


class _Phase:
    """One phase of a loop iteration, on the loop thread only: a
    ``jax.profiler`` host annotation ``kft.engine.<name>`` (inert while
    no trace records; it puts the phase on the device trace's clock, so
    an idle gap of the chip has an owner) and, on exit, the phase's OWN
    wall time added to ``stats()``'s cumulative ``loop_<name>_s``.  A
    phase entered inside another is subtracted from it, so the eleven
    sums tile the thread's wall time; a window is two readings
    subtracted.  The facts of an iteration's phases are kept until the
    next iteration for the record of a slow one (``_note_iteration``).
    The fact ``round`` is the iteration's number, and in the half that
    reads and drains a decode round (``_finish_round``) the number of
    the iteration that dispatched it, whichever iteration reads it: a
    trace's reader pairs a round's dispatch with its wait by it."""

    __slots__ = ("_engine", "_key", "_note", "_t0", "_inner")

    def __init__(self, engine, name, facts):
        self._engine = engine
        self._key = _PHASE_KEY[name]
        self._note = engine._annotate(
            _PHASE_NOTE[name], round=engine._round_no, **facts)
        if facts:
            engine._iter_facts.update(facts)

    def __enter__(self):
        self._note.__enter__()
        self._inner = 0.0
        self._engine._phases.append(self)
        self._t0 = time.perf_counter()
        return self

    def facts(self, **facts):
        """Facts known only at the phase's end."""
        self._note.set_metadata(**facts)
        self._engine._iter_facts.update(facts)

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        stack = self._engine._phases
        stack.pop()
        if stack:
            stack[-1]._inner += dt
        # Loop-thread-owned keys: one writer, stats() copies the dict.
        self._engine._counters[self._key] += dt - self._inner
        self._note.__exit__(*exc)
        return False


class _ReadPhase(_Phase):
    """``round_read``, entered inside ``round_wait`` the moment the read
    a round makes first has returned: ``round_wait``'s own stretch, the
    host blocked on the device, ends here, and what the round reads and
    counts after it is this phase's.  ``handed`` numbers the call whose
    result was read (``_device_has_work``): where it is the LAST call
    that handed the device work, nothing is queued on the device from
    here and the turnaround opens; where the loop got ahead (the next
    round, or a chunk, was dispatched before this read) none does."""

    __slots__ = ("_handed",)

    def __init__(self, engine, handed):
        super().__init__(engine, "round_read", {})
        self._handed = handed

    def __enter__(self):
        super().__enter__()
        if self._handed == self._engine._handed:
            self._engine._ready_at = self._t0
        return self


class _Round:
    """A decode round that has been dispatched and not yet read: what
    ``_dispatch_round`` hands to ``_finish_round``.  ``number`` is the
    iteration that dispatched it and ``handed`` its call's number
    (``_device_has_work``); ``lengths`` and ``adds`` are, a row of
    ``snapshot``, the cache positions before the round and what its
    dispatch added to the entry's ``scheduled``; ``toks`` / ``counts`` /
    ``steps_run`` / ``drafts`` are the program's results on the device,
    and ``held`` its counts taken out of the state (``_COUNT_KEYS``)."""

    __slots__ = ("number", "handed", "width", "live", "snapshot",
                 "lengths", "adds", "t0", "toks", "counts", "steps_run",
                 "drafts", "held")


def _true_token_len(row: np.ndarray) -> int:
    """Real prompt length of a 1-D token row: trailing pad ids (token
    0, the framework-wide pad convention) do not count.  An all-pad row
    keeps its full width — there is no basis to trim it."""
    nz = np.flatnonzero(row)
    return int(nz[-1]) + 1 if nz.size else int(row.shape[0])


def _plain_pool_platform(pool) -> Optional[str]:
    """Platform of the device a paged pool lives on, None for an int8
    ``QTensor`` pool (its attention stays on the gathered view).  Reads
    the sharding, so a described shape answers like a placed array
    (tests/test_tpu_compile.py asks for a chip that is not attached)."""
    from kubeflow_tpu.ops.quantize import QTensor

    if isinstance(pool, QTensor):
        return None
    return next(iter(pool.sharding.device_set)).platform


def _expert_matrices_platform(cfg, params) -> Optional[str]:
    """Platform of the device the expert layers' stacked matrices
    (``moe/wi``, ``moe/wo``) live on; None where the stack holds none, or
    one of them is not what ops/grouped_matmul.py reads where it lies: a
    plain array in the model's bfloat16 (no cast is a copy) whose columns
    fill whole 128-lane tiles.  Reads the sharding, as
    ``_plain_pool_platform``."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from kubeflow_tpu.ops import grouped_matmul
    from kubeflow_tpu.ops.quantize import QTensor

    if cfg.dtype != jnp.bfloat16:
        return None
    platforms = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            nn.unbox(params), is_leaf=lambda x: isinstance(x, QTensor)):
        keys = [getattr(k, "key", None) for k in path]
        if "moe" not in keys or "shared" in keys \
                or keys[-1] not in ("wi", "wo"):
            continue
        if isinstance(leaf, QTensor) or leaf.ndim != 3 \
                or leaf.dtype != jnp.bfloat16 \
                or not grouped_matmul.supports(*leaf.shape[1:]):
            return None
        platforms.add(next(iter(leaf.sharding.device_set)).platform)
    return platforms.pop() if len(platforms) == 1 else None


class DecodeEngine:
    """Continuous-batching decode over a persistent slot-based KV cache.

    Args:
      cfg/params/decode: the loaded model (loaders.lm_generate exposes
        them as ``predict.engine_spec`` — params already staged to HBM).
      slots: concurrent sequences (the persistent cache's row count).
      prefill_len: static prompt width bound; prompts with more REAL
        tokens (trailing pad ids don't count) fall back to the direct
        generate() path.
      max_len: cache columns per slot (default prefill_len +
        decode.max_new_tokens).
      decode_rounds: the most decode steps one ``decode_rounds``
        dispatch may run (the token buffer's static width, docs
        §5.2e).  The width of a round adapts under it
        (``_round_width``); admissions and expiries join between
        rounds.  1 is the same program run one step a dispatch.
      admit_width: how many admissions may be MID-PREFILL concurrently
        — further queued requests wait even when slots are free, so a
        burst of long prompts cannot hoard every slot in a half-filled
        state.  Chunk scheduling among the admitted set is FIFO (the
        oldest admission takes the whole budget until it finishes —
        best TTFT for the head of the line).
      prefill_chunk_tokens: the static chunk program width (clamped to
        prefill_len: ``chunk_w``), ``PREFILL_CHUNK_TOKENS`` by default.
        Between two decode rounds the loop runs at most ONE chunk of
        the oldest mid-prefill admission (a new admission's first chunk
        runs at its claim), and while slots are live only once the
        rounds have saved up its width at ``PREFILL_ROUND_TOKENS`` a
        round: an in-flight slot's longest gap is one round plus one
        chunk regardless of arriving prompt length.
      kv_block_tokens: paged-KV page size in cache positions — also
        the prefix hash/share granularity (prefixes are cached and
        aliased in multiples of this many tokens).
      kv_pool_blocks: device block-pool capacity in pages.  0 (the
        default) sizes it to ``slots x ceil(max_len /
        kv_block_tokens)`` — capacity parity with a slot-reserved
        cache; a smaller pool trades worst-case headroom for more
        co-resident short requests (mixed-length traffic fits far
        more than ``slots`` worth of worst cases), and exhaustion
        sheds typed Overloaded rather than deadlocking: every
        admission reserves its worst-case page count or stays queued.
      prefix_caching: publish/reuse shared prefixes as refcounted
        block aliases (zero-copy; False disables lookup and
        publication, chunked prefill still applies).
      max_queue_depth: bounded admission — a submit arriving with this
        many requests already waiting for slots fails fast with
        Overloaded (HTTP 429 / gRPC RESOURCE_EXHAUSTED) instead of
        queueing unboundedly; 0 = unbounded.  The in-flight cap is
        ``slots`` by construction, so total accepted work is bounded
        by slots + max_queue_depth.
      overload_retry_after_s: the Retry-After hint a shed submission
        carries back to the client.
      mesh: a ``jax.sharding.Mesh`` (serving/sharding.py build_mesh)
        to run tensor-parallel over: params and the paged KV block
        pool are placed with NamedShardings at construction (heads /
        MLP hidden / vocab split under ``partition_rules``; the pool
        shards its kv-head dim) and the SAME AOT programs
        compile SPMD from the argument shardings — the host-owned
        block tables, the step loop, and every admission path are
        untouched.  None (the default) is the single-device engine,
        bit-for-bit the pre-mesh behavior.
      partition_rules: regex partition rules over the param tree
        (default serving/sharding.py LM_PARTITION_RULES); only
        consulted when ``mesh`` is set.
      adapters: a serving/adapters.py ``AdapterRegistry`` to serve
        per-tenant LoRA-style variants from (§5.11).  The stacked
        delta arrays ride INSIDE ``params["adapters"]`` and the
        per-slot row index inside ``state["adapter_ids"]``, so the
        SAME AOT programs serve every variant — mixed-adapter traffic
        co-batches in one continuous batch, ``compiled_programs()``
        never grows a per-adapter entry, and under a mesh the stacked
        axis shards along the ``adapters/...`` partition rules.
        Admission resolves ``inputs["adapter"]`` to a row index (or
        sheds typed 404/429), pins it until release, and seeds the
        request's prefix-digest chain with the adapter's content
        digest so variants never alias each other's KV pages.  None
        (the default) serves the base model exactly as before.
    """

    def __init__(
        self,
        cfg,
        params,
        decode,
        *,
        slots: int = 8,
        prefill_len: int = 256,
        max_len: Optional[int] = None,
        decode_rounds: int = 8,
        admit_width: int = 4,
        prefill_chunk_tokens: int = PREFILL_CHUNK_TOKENS,
        kv_block_tokens: int = 16,
        kv_pool_blocks: int = 0,
        prefix_caching: bool = True,
        host_spill_blocks: int = 0,
        max_queue_depth: int = 0,
        overload_retry_after_s: float = 1.0,
        mesh=None,
        partition_rules=None,
        adapters=None,
        name: str = "engine",
    ):
        import jax

        from kubeflow_tpu.models.generate import (
            index_keys_walked,
            init_paged_state,
            pool_sides,
        )
        from kubeflow_tpu.runtime.prom import REGISTRY

        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self.cfg = cfg
        self.mesh = mesh
        # State of fixed size per SLOT beside the paged pool (the
        # convolution layers of a stack with ``layer_types``): a page
        # alias no longer restores a prefix, and pages alone no longer
        # carry a sequence to the host tier or to another replica.  Until the
        # state is snapshotted per page such a model prefills every
        # prompt whole, and what rests on pages alone is refused by
        # name before any token.
        self._slot_state = cfg.conv_planes > 0
        # A latent pool (``cfg.latent``: ONE array, key and value at
        # once) has no per-slot state, so pages alias and prefixes are
        # reused as for any other model; what MOVES pages between tiers
        # or replicas (two-sided page stacks), the adapters' deltas and
        # the mesh's sharding of kv heads are not built for it, and are
        # refused by name likewise.
        self._pages_stay = (
            f"per-slot state ({cfg.conv_planes} convolution layers)"
            if self._slot_state
            else "a latent pool (key and value in one page)"
            if cfg.latent else None)
        if self._pages_stay:
            for flag, value in (
                    ("host_spill_blocks", host_spill_blocks),
                    ("adapters", adapters), ("mesh", mesh)):
                if value:
                    raise ValueError(
                        f"engine {name!r}: {flag} is not built for a "
                        f"model with {self._pages_stay}")
        if self._slot_state:
            prefix_caching = False
        # A model whose multi-token-prediction module drafts
        # (TransformerConfig.mtp_layers): every decode step verifies a
        # draft made on the device and may yield two tokens; drafting
        # is part of the model, not a dial of the engine (no gate turns
        # it off), and it is greedy.
        self._mtp = int(getattr(cfg, "mtp_layers", 0))
        if self._mtp and decode.temperature > 0:
            raise ValueError(
                f"engine {name!r}: temperature {decode.temperature:g} is "
                "not built for a model whose multi-token-prediction "
                "module drafts (mtp_layers): a draft is taken where it is "
                "the stack's own first choice")
        self._registry = adapters
        self._adapter_version = None
        if adapters is not None:
            # Adapter-array serving (§5.11): the stacked per-tenant
            # delta arrays ride INSIDE the param tree, so every AOT
            # program takes them as ordinary operands (no program-count
            # change) and shard_params below places the stacked axis
            # under the adapters/... partition rules.
            stack, self._adapter_version = adapters.stack_snapshot()
            params = dict(params)
            params["adapters"] = stack
        if mesh is not None:
            # Tensor-parallel placement (serving/sharding.py): a
            # one-time device_put of params + pool; the AOT programs
            # below compile SPMD from these shardings alone.
            from kubeflow_tpu.serving import sharding

            params = sharding.shard_params(
                params, mesh,
                partition_rules or sharding.LM_PARTITION_RULES)
        self.params = params
        self.decode = decode
        self.slots = slots
        self.prefill_len = int(prefill_len)
        if self.prefill_len < 1:
            # A non-positive width silently rejects EVERY prompt via
            # accepts() — all traffic would fall back to the direct
            # path while the engine holds a cache and a thread.  Can
            # arise from the serving entrypoint's derived default when
            # an export config has max_new_tokens >= max_seq_len.
            raise ValueError(
                f"prefill_len must be >= 1, got {self.prefill_len}")
        self.max_len = int(max_len or prefill_len + decode.max_new_tokens)
        if self.max_len <= self.prefill_len:
            raise ValueError(
                f"max_len {self.max_len} leaves no decode room beyond "
                f"prefill_len {self.prefill_len}")
        if getattr(cfg, "max_seq_len", self.max_len) < self.max_len:
            raise ValueError(
                f"max_len {self.max_len} exceeds model max_seq_len "
                f"{cfg.max_seq_len}")
        self.decode_rounds = max(1, int(decode_rounds))
        self.admit_width = max(1, min(int(admit_width), slots))
        self.chunk_w = min(max(1, int(prefill_chunk_tokens)),
                           self.prefill_len)
        self.kv_block_tokens = max(1, int(kv_block_tokens))
        # Per-slot block-table span: enough logical pages to cover
        # max_len positions (a static program shape).
        # The draft layer keeps a token's row one index on (+ 1).
        self._table_blocks = -(-(self.max_len + self._mtp)
                               // self.kv_block_tokens)
        self.kv_pool_blocks = int(kv_pool_blocks) \
            or slots * self._table_blocks
        if self.kv_pool_blocks < 1:
            raise ValueError(
                f"kv_pool_blocks must be >= 1, got {self.kv_pool_blocks}")
        self.prefix_caching = bool(prefix_caching)
        self._prefix_reuse = (
            "off: a page alias does not restore per-slot state, every "
            "prompt is prefilled whole" if self._slot_state
            else "on" if self.prefix_caching else "off: disabled")
        self._moe_layers = sum(
            map(cfg.layer_is_sparse, range(cfg.n_layers))) \
            if cfg.layer_types else 0
        self._index_planes = cfg.kv_planes - cfg.window_planes \
            if cfg.indexed else 0
        # Host-RAM spill tier capacity in pages (§5.10): 0 disables.
        # The tier rides the prefix index (spilled records are looked
        # up by the same chained digests), so it requires caching.
        self.host_spill_blocks = max(0, int(host_spill_blocks)) \
            if self.prefix_caching else 0
        self.max_queue_depth = max(0, int(max_queue_depth))
        self.overload_retry_after_s = overload_retry_after_s
        self._eos = decode.eos_token >= 0
        self._state = init_paged_state(cfg, slots, self.kv_pool_blocks,
                                       self.kv_block_tokens,
                                       decode.kv_cache_dtype)
        # What one resident position costs: keys and values over every
        # plane of the pool (an int8 pool's scales included).
        sides = pool_sides(self._state)
        self.kv_bytes_per_token = sum(
            leaf.nbytes for side in sides
            for leaf in jax.tree_util.tree_leaves(self._state[side])
        ) // (self.kv_pool_blocks * self.kv_block_tokens)
        if mesh is not None:
            from kubeflow_tpu.serving import sharding

            self._state = sharding.shard_paged_state(self._state, mesh)
        # Decided ONCE, from what the engine holds: decode_rounds
        # attends through ops/paged_attention.py when the
        # pool is a plain array on a TPU, a page's rows fill whole
        # 128-lane tiles (the chip's compiler refuses the kernel's page
        # copies otherwise) and no mesh shards the kv heads (a
        # shard_map over that axis is the sound form there; until it
        # exists a mesh keeps the gathered view).  Static for the
        # program; stats()["decode_kernel_steps"] over "steps" is the
        # share of decode steps the kernel served.
        self._paged_kernel = (
            mesh is None
            and _plain_pool_platform(self._state[sides[0]]) == "tpu")
        if self._paged_kernel:
            # Load Pallas here, not inside the first trace: its import
            # took ~3 s on the chip's host and read as compile_s.
            from kubeflow_tpu.ops import paged_attention

            # A latent row is whole 128-lane rows by construction
            # (TransformerConfig.latent_row).
            self._paged_kernel = cfg.latent or paged_attention.supports(
                cfg.head_dim, cfg.n_kv_heads)
        # Whether those decode steps also score their index keys page by
        # page in place, as the program itself decides from the pool.
        self._index_walk = self._paged_kernel and cfg.indexed \
            and index_keys_walked(self._state["cache_index"])
        # Decided once the same way, for BOTH programs: the expert
        # layers' grouped products run through ops/grouped_matmul.py
        # when the expert matrices are plain bfloat16 arrays on a TPU
        # and no mesh shards them (under one the compiler's own
        # ragged_dot is what gets partitioned).  Static for the
        # programs; stats()["grouped_kernel_steps"] over "steps" and
        # "grouped_kernel_chunks" over "prefill_chunks" say what it
        # served.
        self._grouped_kernel = bool(
            mesh is None and cfg.layer_types and cfg.moe_experts
            and _expert_matrices_platform(cfg, params) == "tpu")
        # Host-owned per-slot block tables, passed into every program
        # call; the sentinel value (== pool size) parks writes and
        # reads of unallocated logical pages.  Loop-thread-owned.
        self._tables = np.full(
            (slots, self._table_blocks), self.kv_pool_blocks, np.int32)
        # Paged-KV bookkeeping: physical refcounts, admission
        # reservations, and the block-hashed prefix index.  Mutated by
        # the loop thread ONLY, always under self._lock (submit reads
        # available() for shed attribution).
        self._mgr = BlockManager(self.kv_pool_blocks,
                                 self.kv_block_tokens,
                                 caching=self.prefix_caching,
                                 host_blocks=self.host_spill_blocks)
        self._evict_rec_seen = 0
        self._evict_blk_seen = 0
        # AOT executables, built lazily by the loop thread: the loop
        # calls its programs hundreds of times per second, and
        # the jitted wrapper re-hashes the whole params pytree
        # signature per call (~0.4 ms on the smoke config — comparable
        # to the step itself).  lower().compile() once, then call the
        # executable.  This is also the three-program guarantee made
        # literal: these fields and _rounds_exec below ARE the
        # engine's compiled programs.
        self._chunk_exec = None
        # Disaggregated-serving KV import program (kv_import): built
        # the first time a handoff payload arrives; runs once per
        # imported request, never in the step loop.
        self._import_exec = None
        # Decode rounds: the while_loop
        # executable, the double-buffered device-side block-table
        # snapshot (re-uploaded in the overlap window; any host-table
        # mutation marks it dirty), the table sharding the SPMD
        # executable expects (None = pass the host array per dispatch),
        # the adaptive round width, the realized steps-per-round
        # reservoir, and the per-token pace EMA the deadline clamp
        # reads.  All loop-thread-owned.
        self._rounds_exec = None
        self._tables_dev = None
        self._tables_dirty = True
        self._tables_sharding = None
        self._round_k = self.decode_rounds
        self._round_steps: List[int] = []
        self._step_pace_ema: Optional[float] = None
        # Per-tenant fair admission (§5.11): last-admitted sequence
        # per adapter key ("" = base traffic).  Mutated only under
        # self._lock by the admission pop.
        self._fair_last: Dict[str, int] = {}
        self._fair_seq = 0

        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        # Streaming delivery signal: submit_stream() readers wait here
        # and _drain_one notifies after each materialized emission, so
        # a streamed token reaches its client one drain after the
        # device produced it (no polling the hot loop).
        self._emit = threading.Condition(self._lock)
        self._queue: List[dict] = []
        self._stopped = False
        self._drain_deadline: Optional[float] = None
        # Host-side slot table: None = free, else the live request entry.
        self._slot_req: List[Optional[dict]] = [None] * slots
        # Admitted entries whose prompts are still chunk-prefilling
        # (FIFO — the oldest admission finishes first, best TTFT).
        # Loop-thread-owned; the admission pop reads only its length.
        self._prefilling: List[dict] = []
        # Prompt tokens of prefill the rounds since the last chunk have
        # saved up (PREFILL_ROUND_TOKENS a round; _iterate).
        self._prefill_credit = 0
        # (tokens_array, [(slot, entry), ...], counts, handed) emissions
        # not yet delivered, in the order their calls were handed to the
        # device, and the entries the current round advances.
        self._pending: List[tuple] = []
        self._advancing: List[dict] = []
        # The decode rounds dispatched and not yet read, oldest first:
        # one between two iterations where the loop runs ahead, two from
        # the next round's dispatch to the older one's drain.
        self._unread: List[_Round] = []
        # Two sets of spare arrays for the counts a round leaves in the
        # state (_COUNT_KEYS): a dispatch takes the round's own out and
        # puts a spare set in, the read gives its set back, and one
        # round in flight needs no third.
        self._count_keys = tuple(
            key for key in _COUNT_KEYS if key in self._state)
        self._count_spares = [
            {key: jax.device_put(
                np.zeros(self._state[key].shape, self._state[key].dtype),
                self._state[key].sharding)
             for key in self._count_keys} for _ in range(2)]
        # Counters (mutated by the loop thread, snapshotted under the
        # lock — the same locked-snapshot discipline MicroBatcher uses).
        self._counters = {
            "requests": 0, "tokens": 0, "steps": 0, "prefills": 0,
            "occupancy_sum": 0, "busy_s": 0.0, "in_flight": 0,
            "shed": 0, "expired": 0,
            "prefix_hits": 0, "prefix_misses": 0, "prefix_evictions": 0,
            "prefill_chunks": 0, "cached_tokens": 0, "prompt_tokens": 0,
            "prefill_positions_held": 0, "prefill_positions_scored": 0,
            "mtp_drafted": 0, "mtp_accepted": 0, "mtp_steps": 0,
            "kv_evictions": 0, "kv_shed_no_blocks": 0,
            "handoff_pages_out": 0, "handoff_pages_in": 0,
            "fused_rounds": 0, "fused_steps_wasted": 0,
            "decode_kernel_steps": 0,
            "grouped_kernel_steps": 0, "grouped_kernel_chunks": 0,
            "spill_pages_out": 0, "spill_pages_in": 0,
            "parked_sessions": 0, "fetches": 0, "experts_touched": 0,
            **dict.fromkeys(_PAIR_KEYS, 0),
            **dict.fromkeys(_SPARSE_KEYS, 0),
            **dict.fromkeys(_SUM_KEYS, 0),
        }
        import jax

        self._annotate = jax.profiler.TraceAnnotation
        self._phases: List[_Phase] = []  # loop-thread-owned stack
        self._loop_pushed = dict.fromkeys(_PHASE_KEY.values(), 0.0)
        # Loop-thread-owned, as the stack: the number its phases are
        # stamped with (_Phase), how many calls have handed the device
        # work, when the open turnaround's first result landed (None:
        # the device has work, or the loop went idle), the facts of this
        # iteration's phases, the running mean of an iteration's wall
        # time and how many iterations are in it, the compile seconds
        # it has seen and when it last logged a slow iteration.
        self._round_no = 0
        self._handed = 0
        self._ready_at: Optional[float] = None
        self._iter_facts: Dict[str, Any] = {}
        self._iter_mean = 0.0
        self._iter_samples = 0
        self._compile_seen = 0.0
        self._slow_logged_at = float("-inf")
        self._step_times: List[float] = []   # bounded reservoirs
        self._chunk_times: List[float] = []
        self._gap_times: List[float] = []
        self._last_step_end: Optional[float] = None
        self._metric_name = name
        self._occ_gauge = REGISTRY.gauge(
            "kft_engine_active_slots",
            "decode engine live slots, by engine")
        self._queue_gauge = REGISTRY.gauge(
            "kft_engine_queue_depth",
            "decode engine admission queue depth, by engine")
        self._tok_counter = REGISTRY.counter(
            "kft_engine_tokens_total",
            "tokens emitted by the decode engine, by engine")
        self._step_hist = REGISTRY.histogram(
            "kft_engine_step_seconds",
            "decode engine per-step (= per-token) latency, by engine",
            buckets=_STEP_BUCKETS,
        ).declare(engine=name)
        self._hits_ctr = REGISTRY.counter(
            PREFIX_HITS_TOTAL, PREFIX_HITS_HELP)
        self._misses_ctr = REGISTRY.counter(
            PREFIX_MISSES_TOTAL, PREFIX_MISSES_HELP)
        self._evict_ctr = REGISTRY.counter(
            PREFIX_EVICTIONS_TOTAL, PREFIX_EVICTIONS_HELP)
        self._chunks_ctr = REGISTRY.counter(
            PREFILL_CHUNKS_TOTAL, PREFILL_CHUNKS_HELP)
        self._kv_blocks_gauge = REGISTRY.gauge(
            KV_BLOCKS_GAUGE, KV_BLOCKS_HELP)
        self._kv_used_gauge = REGISTRY.gauge(
            KV_BLOCKS_USED_GAUGE, KV_BLOCKS_USED_HELP)
        self._kv_evict_ctr = REGISTRY.counter(
            KV_EVICTIONS_TOTAL, KV_EVICTIONS_HELP)
        self._kv_shed_ctr = REGISTRY.counter(
            KV_SHED_TOTAL, KV_SHED_HELP)
        self._mtp_drafted_ctr = REGISTRY.counter(
            MTP_DRAFTED_TOTAL, MTP_DRAFTED_HELP)
        self._mtp_accepted_ctr = REGISTRY.counter(
            MTP_ACCEPTED_TOTAL, MTP_ACCEPTED_HELP)
        self._mesh_gauge = REGISTRY.gauge(
            MESH_DEVICES_GAUGE, MESH_DEVICES_HELP)
        self._handoff_ctr = REGISTRY.counter(
            HANDOFF_PAGES_TOTAL, HANDOFF_PAGES_HELP)
        self._fused_rounds_ctr = REGISTRY.counter(
            FUSED_ROUNDS_TOTAL, FUSED_ROUNDS_HELP)
        self._fused_wasted_ctr = REGISTRY.counter(
            FUSED_WASTED_TOTAL, FUSED_WASTED_HELP)
        self._kernel_steps_ctr = REGISTRY.counter(
            DECODE_KERNEL_STEPS_TOTAL, DECODE_KERNEL_STEPS_HELP)
        self._sparse_reads_ctr = REGISTRY.counter(
            SPARSE_READS_TOTAL, SPARSE_READS_HELP)
        self._kv_spilled_gauge = REGISTRY.gauge(
            KV_SPILLED_GAUGE, KV_SPILLED_HELP)
        self._host_tier_gauge = REGISTRY.gauge(
            HOST_TIER_GAUGE, HOST_TIER_HELP)
        self._kv_spill_ctr = REGISTRY.counter(
            KV_SPILL_TOTAL, KV_SPILL_HELP)
        self._adapter_req_ctr = REGISTRY.counter(
            ADAPTER_REQUESTS_TOTAL, ADAPTER_REQUESTS_HELP)
        self._loop_ctr = REGISTRY.counter(
            LOOP_SECONDS_TOTAL, LOOP_SECONDS_HELP)
        self._queue_wait_ctr = REGISTRY.counter(
            QUEUE_WAIT_TOTAL, QUEUE_WAIT_HELP)
        self._prefill_span_ctr = REGISTRY.counter(
            PREFILL_SPAN_TOTAL, PREFILL_SPAN_HELP)
        self._compile_ctr = REGISTRY.counter(
            COMPILE_SECONDS_TOTAL, COMPILE_SECONDS_HELP)
        # Fault-layer series: same names as the static batchers', so
        # shed/expired rates read uniformly across batching planes.
        self._shed_ctr = REGISTRY.counter(SHED_TOTAL, SHED_HELP)
        self._expired_ctr = REGISTRY.counter(EXPIRED_TOTAL, EXPIRED_HELP)
        self._occ_gauge.set(0, engine=name)
        self._queue_gauge.set(0, engine=name)
        self._kv_blocks_gauge.set(self.kv_pool_blocks, engine=name)
        self._kv_used_gauge.set(0, engine=name)
        self._kv_spilled_gauge.set(0, engine=name)
        self._host_tier_gauge.set(self.host_spill_blocks, engine=name)
        from kubeflow_tpu.serving.sharding import mesh_devices

        self._mesh_gauge.set(mesh_devices(mesh), engine=name)
        # Last values pushed to the gauges — the step loop only touches
        # the (locked) registry when a value actually changes.
        self._occ_last = 0
        self._queue_last = 0
        self._kv_used_last = 0
        self._kv_spilled_last = 0
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"decode-engine-{name}")
        self._thread.start()

    # -- client surface ---------------------------------------------------

    def accepts(self, inputs: Dict[str, Any]) -> bool:
        """ModelServer routing hook: prompts whose REAL token count
        (an explicit ``prompt_len``, else the width minus trailing pad
        ids) exceeds the static prefill width fall back to the direct
        generate() path.  A short prompt arriving right-padded — e.g.
        from a client that pads to a fixed wire shape — is admitted at
        its true length, not rejected for its padded width."""
        tokens = np.asarray(inputs.get("tokens", ()))
        if tokens.ndim == 0 or tokens.size == 0:
            return False
        row = tokens.reshape(-1)
        if "prompt_len" in inputs:
            length = int(np.asarray(inputs["prompt_len"]).reshape(()))
            if not 0 < length <= row.shape[0]:
                return False
        else:
            length = _true_token_len(row)
        # A resume's delivered tokens join the context, so they count
        # against the static prefill width too.
        length += int(np.asarray(
            inputs.get("resume_tokens", ())).size)
        return bool(0 < length <= self.prefill_len)

    def submit(self, inputs: Dict[str, Any],
               deadline: Optional[float] = None) -> Dict[str, Any]:
        """One request: tokens [t] or [1, t]; optional per-request
        ``max_new_tokens`` (<= engine headroom), sampling ``seed``, and
        ``prompt_len`` (real token count of a right-padded prompt —
        without it, trailing pad ids (token 0) are trimmed, so a padded
        short prompt is neither rejected nor over-prefilled, and never
        generates with pad tokens in its context).  Blocks until the
        completion is ready; returns {"tokens": [1, true_len + emitted]}.
        With ``return_timing`` truthy the result also carries
        ``ttft_s`` / ``latency_s`` / ``cached_tokens`` (bench surface).

        ``resume_tokens`` (mid-generation failover, the router's
        replay payload): tokens a PRIOR attempt of this request
        already emitted.  They join the prompt as ordinary context —
        the whole resume is one chunked prefill that aliases whatever
        prefix blocks this replica has cached — and the budget shrinks
        by their count, so the engine emits exactly the SUFFIX an
        uninterrupted run would have produced after them (greedy
        decode is prefix-deterministic, which is what makes the
        spliced stream token-identical).  A resume whose tokens
        already exhaust the budget or end at EOS resolves immediately
        as a completed generation.

        ``deadline`` (absolute faults.monotonic() instant) is enforced
        everywhere the request lives: expired-on-arrival raises here,
        an expired queued request is failed before admission, and an
        expired IN-FLIGHT request is retired mid-generation through
        the deterministic-retirement path — its slot frees for the
        next admission while its lagged device emissions are dropped
        on the floor, exactly like a normally-retired slot's."""
        entry = self._admit(inputs, deadline)
        entry["event"].wait()
        if entry["err"] is not None:
            raise entry["err"]
        return entry["out"]

    def prefill_export(self, inputs: Dict[str, Any],
                       deadline: Optional[float] = None
                       ) -> Dict[str, Any]:
        """Disaggregated serving, prefill tier: admit the prompt as an
        ordinary request clamped to ONE generated token (prefill is
        the whole job — the single sampled token proves the pages are
        complete and is recomputed by the decode tier anyway) and
        return the result with its finished full-block pages attached
        under ``kv_handoff`` (see _attach_export).  Prompts too short
        to cover one full page return no payload — the caller falls
        back to the untiered path."""
        fwd = dict(inputs)
        fwd["kv_export"] = True
        fwd["max_new_tokens"] = 1
        return self.submit(fwd, deadline=deadline)

    def submit_stream(self, inputs: Dict[str, Any],
                      deadline: Optional[float] = None):
        """Streaming twin of :meth:`submit`: admits the request (same
        validation, deadlines, resume semantics, typed sheds — all
        raised HERE, before any byte is produced) and returns
        ``(meta, iterator)``.  ``meta`` tells the transport layer what
        failover the request supports — ``resumable`` (greedy export:
        a replay with ``resume_tokens`` is token-identical) and
        ``seeded`` (an explicit sampling seed was recorded: a replay
        FROM SCRATCH reproduces the identical stream, so a proxy can
        skip already-delivered tokens) — plus the admitted context
        width and granted budget.  The iterator yields lists of newly
        emitted token ints as the drain materializes them and raises
        the request's typed error (DeadlineExceeded, BatcherClosed)
        mid-stream if it fails after admission."""
        entry = self._admit(inputs, deadline)
        meta = {
            "resumable": self.decode.temperature <= 0.0,
            "seeded": inputs.get("seed") is not None,
            "prompt_tokens": int(entry["tokens"].shape[1]),
            "max_new_tokens": entry["new"],
        }
        if self._mtp:
            # What the model's own module drafted for this request, as
            # the rounds hand it over: (index of the emitted token a
            # round began at, the draft each of the round's tokens was
            # held against, -1 where none was: the second of a pair).
            meta["mtp_drafts"] = entry["mtp_drafts"]

        def stream():
            sent = 0
            while True:
                with self._emit:
                    n = len(entry["emitted"])
                    if n <= sent and not entry["event"].is_set():
                        self._emit.wait(timeout=0.02)
                        continue
                if n > sent:
                    chunk = [int(t) for t in entry["emitted"][sent:n]]
                    sent = n
                    yield chunk
                if entry["event"].is_set() \
                        and sent >= len(entry["emitted"]):
                    if entry["err"] is not None:
                        raise entry["err"]
                    return

        return meta, stream()

    def _admit(self, inputs: Dict[str, Any],
               deadline: Optional[float]) -> dict:
        """Validate + enqueue one request (submit/submit_stream share
        this); returns the live entry whose ``event`` resolves it."""
        tokens = np.asarray(inputs["tokens"], np.int32)
        if tokens.ndim == 1:
            tokens = tokens[None]
        n, width = tokens.shape
        if n != 1:
            raise ValueError(
                f"DecodeEngine.submit takes one prompt per call (got "
                f"batch dim {n}); submit rows separately")
        if "prompt_len" in inputs:
            length = int(np.asarray(inputs["prompt_len"]).reshape(()))
            if not 0 < length <= width:
                raise ValueError(
                    f"prompt_len {length} outside (0, {width}] "
                    f"(the tokens width)")
        else:
            length = _true_token_len(tokens[0])
        if length <= 0:
            raise ValueError(
                f"true prompt length {length} must be positive")
        tokens = np.ascontiguousarray(tokens[:, :length])
        # Mid-generation resume: a prior attempt's delivered tokens
        # join the context (one ordinary chunked prefill — they alias
        # cached prefix blocks where this replica has them) and the
        # budget shrinks by their count, so only the suffix an
        # uninterrupted run would produce is emitted.
        resume = np.asarray(
            inputs.get("resume_tokens", ()), np.int32).reshape(-1)
        resume_len = int(resume.shape[0])
        total_budget = int(np.asarray(inputs.get(
            "max_new_tokens", self.decode.max_new_tokens)).reshape(()))
        if total_budget < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {total_budget}")
        total_budget = min(total_budget, self.decode.max_new_tokens)
        if resume_len:
            # Chaos hook: the resume admission path (sleep = slow
            # failover, raise = resume rejected — the router's replay
            # layer must surface it rather than hang the splice).
            faults.fire("engine.resume")
            if resume_len > total_budget:
                raise ValueError(
                    f"resume_tokens carries {resume_len} tokens but "
                    f"the budget is {total_budget}")
            tokens = np.concatenate([tokens, resume[None]], axis=1)
            length += resume_len
            if resume_len == total_budget or (
                    self._eos
                    and bool(np.any(resume == self.decode.eos_token))):
                # The prior attempt already finished the generation
                # (died between its last token and the done marker):
                # resolve as a completed request, nothing to emit.
                return self._completed_entry(tokens, inputs)
        if not 0 < length <= self.prefill_len:
            raise ValueError(
                f"true context length {length} (prompt + "
                f"{resume_len} resumed) outside "
                f"(0, {self.prefill_len}] (engine prefill width)")
        # Same budget contract as every other serving path: the export
        # config's max_new_tokens is the ceiling (a client cannot buy a
        # bigger completion than the model advertises), and the cache
        # headroom caps it further — both against the TRUE length.
        new = min(total_budget - resume_len, self.max_len - length)
        seed = int(np.asarray(inputs.get("seed", 0)).reshape(()))
        # Disaggregated serving: ``kv_export`` marks a prefill-tier
        # request whose result must carry its finished KV pages
        # (:prefill route); ``kv_handoff`` is the decode-tier import
        # payload those pages arrive as.  Both validated HERE so a
        # malformed payload answers 400 before any device work.
        if self._pages_stay:
            for key in ("kv_export", "kv_handoff", "park_kv"):
                if inputs.get(key):
                    raise ValueError(
                        f"{key} is not built for a model with "
                        f"{self._pages_stay}: its pages are not moved")
        export = bool(inputs.get("kv_export"))
        handoff = self._parse_handoff(inputs.get("kv_handoff"), length)
        if deadline is not None and faults.monotonic() >= deadline:
            with self._lock:
                self._counters["expired"] += 1
            self._expired_ctr.inc(batcher=self._metric_name)
            raise DeadlineExceeded(
                f"deadline expired before engine "
                f"{self._metric_name!r} admission")
        # Adapter-array resolution (§5.11): name -> stacked row index,
        # PINNED from here to release so LRU eviction can never
        # recycle a row under an in-flight request.  Unknown names
        # shed typed 404, slot exhaustion / an open load breaker 429 —
        # all raised HERE, before any queue state exists.  Every
        # terminal path below must unpin (_unpin_adapter is
        # idempotent), which is what makes "evictable" == "no live
        # request" exact.
        adapter_name = inputs.get("adapter")
        adapter_idx, adapter_salt, adapter_pin = 0, b"", None
        if adapter_name:
            adapter_name = str(adapter_name)
            if self._registry is None:
                raise AdapterNotFound(
                    f"engine {self._metric_name!r} serves no adapters "
                    f"(requested {adapter_name!r})")
            adapter_idx, digest = self._registry.acquire(adapter_name)
            adapter_pin = adapter_idx
            # KV is adapter-SCOPED by the CONTENT digest (stable
            # across replicas, unlike the row index): variants never
            # alias each other's cached pages, while the same adapter
            # on two replicas hashes identically for :fetch_kv.
            adapter_salt = bytes.fromhex(digest)
            self._adapter_req_ctr.inc(
                engine=self._metric_name, adapter=adapter_name)
        else:
            adapter_name = None
        # Trace context captured on the transport thread; the loop
        # thread stamps spans from perf readings at drain time (never
        # per token), so the hot step loop stays untouched and a
        # disabled tracer costs one None check per site.  The perf
        # readings themselves are always taken (submit, slot claim,
        # first token, delivery): the same stamps feed the spans and
        # the always-kept queue-wait / prefill-span sums of stats().
        # Worst-case paged-KV reservation: every position the request
        # could ever write (prompt + full budget) in whole pages.
        # Reserving it at admission is what makes block exhaustion a
        # typed shed instead of a mid-flight deadlock.
        res_blocks = -(-(length + new + self._mtp) // self.kv_block_tokens)
        trace_ctx = tracing.current_ctx()
        entry = {
            "tokens": tokens, "new": new, "seed": seed,
            "emitted": [], "scheduled": 0, "slot": None,
            "trace": trace_ctx,
            "t_perf": time.perf_counter(), "t_claim_perf": None,
            "t_first_perf": None,
            "prefilling": False, "pos": 0, "cached": 0,
            "res_blocks": res_blocks, "res_left": 0, "blocks": [],
            "released": False,
            "export": export, "handoff": handoff,
            "park": bool(inputs.get("park_kv")), "spill_in": None,
            "adapter": adapter_idx, "adapter_salt": adapter_salt,
            "adapter_name": adapter_name,
            "mtp_drafts": [],
            "deadline": deadline,
            "want_timing": bool(inputs.get("return_timing")),
            "event": threading.Event(), "out": None, "err": None,
            "t": faults.monotonic(), "t_first": None,
        }
        if adapter_pin is not None:
            entry["adapter_pin"] = adapter_pin
        with self._lock:
            if self._stopped:
                self._unpin_adapter(entry)
                raise BatcherClosed(
                    f"engine {self._metric_name!r} is closed")
            if res_blocks > self.kv_pool_blocks:
                # The request's worst case can NEVER fit this pool —
                # queueing it would wedge the admission head forever,
                # so shed it typed (the client can retry a smaller
                # budget; capacity planning reads the counter).
                self._counters["shed"] += 1
                self._counters["kv_shed_no_blocks"] += 1
                self._shed_ctr.inc(batcher=self._metric_name)
                self._kv_shed_ctr.inc(engine=self._metric_name)
                self._unpin_adapter(entry)
                raise Overloaded(
                    f"request needs {res_blocks} KV blocks but engine "
                    f"{self._metric_name!r}'s pool holds "
                    f"{self.kv_pool_blocks}",
                    retry_after_s=self.overload_retry_after_s)
            if self.max_queue_depth \
                    and len(self._queue) >= self.max_queue_depth:
                # Bounded admission: the wait line is full — fail fast
                # instead of queueing unboundedly (under overload a
                # 429 now beats a 504 later).  Attribute the shed:
                # when the block pool (tokens resident), not the slot
                # count, is what is binding, the kv counter tells the
                # operator to grow --kv_pool_blocks rather than slots.
                self._counters["shed"] += 1
                if self._mgr.available() < res_blocks:
                    self._counters["kv_shed_no_blocks"] += 1
                    self._kv_shed_ctr.inc(engine=self._metric_name)
                self._shed_ctr.inc(batcher=self._metric_name)
                self._unpin_adapter(entry)
                raise Overloaded(
                    f"engine {self._metric_name!r} admission queue "
                    f"full ({len(self._queue)} waiting, "
                    f"{self.slots} slots busy)",
                    retry_after_s=self.overload_retry_after_s)
            self._queue.append(entry)
            self._set_queue_gauge(len(self._queue))
            self._work.notify()
        return entry

    def _completed_entry(self, tokens: np.ndarray,
                         inputs: Dict[str, Any]) -> dict:
        """A resume whose prior attempt already finished (budget spent
        or EOS delivered, only the done marker lost): resolve without
        touching the loop — the full context IS the result."""
        entry = {
            "tokens": tokens, "new": 0, "emitted": [],
            "out": {"tokens": tokens}, "err": None,
            "event": threading.Event(),
        }
        if inputs.get("return_timing"):
            entry["out"]["ttft_s"] = 0.0
            entry["out"]["latency_s"] = 0.0
            entry["out"]["cached_tokens"] = 0
        entry["event"].set()
        return entry

    def compiled_programs(self) -> Dict[str, int]:
        """How many device programs this engine has compiled — by
        construction at most one chunked-prefill and one decode-rounds
        executable (the build sites are None-guarded), so a healthy
        engine reports at most {"chunked_prefill": 1,
        "decode_rounds": 1} for its whole lifetime (ONE
        ``decode_rounds`` executable serves every round width — the
        per-round step cap is a traced operand).  There is no
        prefix-copy program: shared-prefix reuse is host-side
        block-table aliasing.  A decode-tier engine that has imported
        a disaggregated KV handoff additionally reports ``kv_import``
        (once compiled) — the one-per-request page-scatter program;
        engines that never see a handoff keep the exact two-key
        shape."""
        out = {"chunked_prefill": int(self._chunk_exec is not None),
               "decode_rounds": int(self._rounds_exec is not None)}
        if self._import_exec is not None:
            out["kv_import"] = 1
        return out

    def adapter_info(self) -> List[Dict[str, Any]]:
        """Resident adapters (name/digest/index/pins) for /readyz
        advertisement and the router's digest-affinity pick; empty
        when this engine serves no adapters (§5.11)."""
        return self._registry.loaded() if self._registry is not None \
            else []

    def stats(self) -> Dict[str, Any]:
        """Locked snapshot of the engine counters: occupancy, queue
        depth, throughput, per-token (= per-step) latency, prefix-cache
        effectiveness, and prefill-interference bounds."""
        c, extra = locked_snapshot(
            self._lock, self._counters,
            lambda: {
                "queue_depth": len(self._queue),
                "active_slots": sum(
                    r is not None for r in self._slot_req),
                "kv_used": self._mgr.used_blocks(),
                "host_used": self._mgr.host_used_blocks(),
                "step_times": list(self._step_times),
                "chunk_times": list(self._chunk_times),
                "gap_times": list(self._gap_times),
                "round_steps": list(self._round_steps),
            })
        steps = c["steps"]

        # Sort each reservoir ONCE, outside the lock: the lock only
        # pays the list copies, and every percentile below reads
        # the one sorted copy — the old shape re-sorted the same
        # 4096-entry reservoir per pct() call while a hot /stats +
        # /metrics scrape pattern held the decode loop's lock.
        times = sorted(extra["step_times"])
        gaps = sorted(extra["gap_times"])
        chunks = sorted(extra["chunk_times"])
        rounds = sorted(extra["round_steps"])

        def pct(sorted_values, q):
            if not sorted_values:
                return 0.0
            return round(sorted_values[min(len(sorted_values) - 1,
                                           int(len(sorted_values) * q))]
                         * 1e3, 3)

        def pct_raw(sorted_values, q):
            if not sorted_values:
                return 0
            return sorted_values[min(len(sorted_values) - 1,
                                     int(len(sorted_values) * q))]

        prompt_toks = c["prompt_tokens"]
        out = {
            "requests": c["requests"],
            "tokens": c["tokens"],
            "steps": steps,
            "prefills": c["prefills"],
            "slots": self.slots,
            "active_slots": extra["active_slots"],
            "queue_depth": extra["queue_depth"],
            # Admitted but not yet delivered.  THIS is the drain signal:
            # deterministic retirement frees a slot at dispatch (before
            # the lagged emission reaches its client), so active_slots
            # can touch zero while completions are still in flight.
            "in_flight_requests": c["in_flight"],
            # Fault-layer outcomes: admissions refused at the queue cap
            # and requests failed by their deadline (queued or
            # in-flight) — the chaos scenario's primary assertions.
            "shed": c["shed"],
            "deadline_expired": c["expired"],
            # Prefix cache: how much prompt compute block-table
            # aliasing saved.  cached_token_ratio is the one-glance
            # effectiveness number (also exported per-replica to the
            # fleet — see ModelServer.refresh_gauges).
            "prefix_hits": c["prefix_hits"],
            "prefix_misses": c["prefix_misses"],
            "prefix_evictions": c["prefix_evictions"],
            "cached_prompt_tokens": c["cached_tokens"],
            "prompt_tokens": prompt_toks,
            "cached_token_ratio": round(
                c["cached_tokens"] / prompt_toks, 4)
            if prompt_toks else 0.0,
            # Paged KV pool: capacity is tokens RESIDENT, not slots.
            # kv_utilization is the one-glance "how full is this
            # chip's serving memory" number (the fleet CACHE% story
            # extended to capacity); the shed counter attributes
            # overload to the pool rather than the slot count.
            "kv_blocks": self.kv_pool_blocks,
            "kv_blocks_used": extra["kv_used"],
            "kv_block_tokens": self.kv_block_tokens,
            # The pool's leading axis, one plane per (loop step,
            # layer), and what a resident position costs over all of
            # them: static facts of the model, not serving knobs.
            "loop_steps": self.cfg.loop_steps,
            "kv_planes": self.cfg.kv_planes,
            "kv_bytes_per_token": self.kv_bytes_per_token,
            # What a stack with ``layer_types`` holds beside the pool
            # (zeros for every other model): the layers with a
            # convolution state per slot and its bytes over all slots;
            # the sparse layers, their experts and experts a token; and
            # the device's own count of distinct experts that got a row,
            # summed over sparse layers and decode steps (over
            # moe_layers x moe_experts x steps: the share of the
            # experts' weights a step reads).
            "conv_planes": self.cfg.conv_planes,
            "conv_state_bytes": int(self._state["conv"].nbytes)
            if "conv" in self._state else 0,
            "moe_layers": self._moe_layers,
            # The experts whose weights this engine HOLDS (a share's 16
            # of 512 routed ones; every expert where none is cut): what a
            # step can touch.
            "moe_experts": self.cfg.moe_held if self._moe_layers else 0,
            "moe_top_k": self.cfg.moe_top_k if self._moe_layers else 0,
            "experts_touched": c["experts_touched"],
            # A latent pool's one row a token and plane, key and value
            # at once (0 for a k / v pool); the chip's share of the
            # experts and those that need no weights; and the device's
            # own counts of the (row, choice) pairs of the decode
            # steps, by where they fell (zeros where every expert is
            # held and has weights: all pairs are held then).
            "latent_bytes_per_token": self.kv_bytes_per_token
            if self.cfg.latent else 0,
            "moe_experts_held": self.cfg.moe_held
            if self._moe_layers else 0,
            "moe_routed_experts": self.cfg.moe_experts
            if self._moe_layers else 0,
            "moe_zero_experts": self.cfg.moe_zero_experts
            if self._moe_layers else 0,
            **{key: c[key] for key in _PAIR_KEYS},
            # What the decode steps of a stack with an indexer or sliding
            # layers read of those planes (zeros for every other model).
            **{key: c[key] for key in _SPARSE_KEYS},
            "prefix_reuse": self._prefix_reuse,
            "kv_block_evictions": c["kv_evictions"],
            "kv_shed_no_blocks": c["kv_shed_no_blocks"],
            "tokens_resident": extra["kv_used"] * self.kv_block_tokens,
            "kv_utilization": round(
                extra["kv_used"] / self.kv_pool_blocks, 4)
            if self.kv_pool_blocks else 0.0,
            # Hierarchical KV (§5.10): host spill-tier occupancy and
            # flow.  tokens_addressable is the two-tier capacity story
            # — positions servable without a cold prefill, device pool
            # PLUS host tier; kv_spill_ratio is host-tier occupancy
            # (used / capacity) — the same number the
            # kft_serving_kv_spill_ratio gauge and the fleet-status
            # SPILL% column render.
            "host_spill_blocks": self.host_spill_blocks,
            "host_tier_used": extra["host_used"],
            "kv_spill_pages_out": c["spill_pages_out"],
            "kv_spill_pages_in": c["spill_pages_in"],
            "parked_sessions": c["parked_sessions"],
            "kv_fetches": c["fetches"],
            "tokens_addressable": (self.kv_pool_blocks
                                   + self.host_spill_blocks)
            * self.kv_block_tokens,
            "kv_spill_ratio": round(
                extra["host_used"] / self.host_spill_blocks, 4)
            if self.host_spill_blocks else 0.0,
            # Multi-chip serving: how many devices this engine's mesh
            # spans (1 = single-device) and how many paged-KV pages
            # have crossed the disaggregated prefill/decode boundary
            # in each direction.
            "mesh_devices": self._mesh_devices(),
            "handoff_pages_out": c["handoff_pages_out"],
            "handoff_pages_in": c["handoff_pages_in"],
            # The model's own multi-token-prediction module
            # (TransformerConfig.mtp_layers): drafts a decode step
            # verified, drafts taken (steps that yielded two tokens) and
            # the decode steps that drafted.
            "mtp_drafted": c["mtp_drafted"],
            "mtp_accepted": c["mtp_accepted"],
            "mtp_steps": c["mtp_steps"],
            # Fused decode rounds (docs §5.2e): rounds dispatched,
            # early-exit slot-steps that delivered nothing, and the
            # realized steps-per-round distribution — how much of the
            # configured width the device actually ran before every
            # slot froze.
            "decode_rounds": self.decode_rounds,
            "fused_rounds": c["fused_rounds"],
            "fused_steps_wasted": c["fused_steps_wasted"],
            # Steps whose program held the paged attention kernel
            # (decode_rounds on a TPU pool): over "steps"
            # it is the share the kernel served, 0 off the chip.
            "decode_kernel_steps": c["decode_kernel_steps"],
            # Decode steps and prefill chunks whose expert layers ran
            # their grouped products through ops/grouped_matmul.py:
            # over "steps" / "prefill_chunks" the share it served.
            "grouped_kernel_steps": c["grouped_kernel_steps"],
            "grouped_kernel_chunks": c["grouped_kernel_chunks"],
            "steps_per_round_p50": pct_raw(rounds, 0.50),
            "steps_per_round_p99": pct_raw(rounds, 0.99),
            # Which AOT programs exist — the four-program guarantee,
            # observable over the :stats route (the hermetic engine
            # e2e asserts it end to end).
            "compiled_programs": self.compiled_programs(),
            # Chunked prefill: calls made and how long the host took
            # to DISPATCH one (the call returns at enqueue; the chunk's
            # compute is in the next round's wait) — one chunk is the
            # most an arriving prompt may stall in-flight decode per
            # scheduling turn.
            "prefill_chunks": c["prefill_chunks"],
            "prefill_chunk_p95_ms": pct(chunks, 0.95),
            # What the chunks' attention cost, summed over chunks: the
            # positions a chunk's last real row may see (its start + its
            # real tokens) and the positions of the slot's view the
            # program visited for it (that bound in whole key tiles,
            # the table's length where the program makes one pass:
            # generate.view_positions_scored, no device read).
            "prefill_positions_held": c["prefill_positions_held"],
            "prefill_positions_scored": c["prefill_positions_scored"],
            # Where the time goes, cumulative (see _SUM_KEYS): the
            # loop's phases, the turnaround between two rounds, the
            # loop thread's CPU seconds, the slow iterations, queue
            # wait, prefill span, compiles.
            **{key: c[key] for key in _SUM_KEYS},
            "mean_occupancy": round(c["occupancy_sum"] / steps, 2)
            if steps else 0.0,
            "tokens_per_sec": round(c["tokens"] / c["busy_s"], 1)
            if c["busy_s"] else 0.0,
            "token_latency_p50_ms": pct(times, 0.50),
            "token_latency_p95_ms": pct(times, 0.95),
            # Wall time between consecutive step-call completions while
            # slots were live — the client-visible inter-token gap,
            # INCLUDING whatever admission/prefill work ran in between.
            # Bounded by one round plus one chunk; a full-prefill stall
            # would spike the max.
            "inter_token_gap_p50_ms": pct(gaps, 0.50),
            "inter_token_gap_max_ms": round(gaps[-1] * 1e3, 3)
            if gaps else 0.0,
        }
        if self._registry is not None:
            # Adapter-array serving (§5.11): registry occupancy plus
            # the resident name/digest list the fleet layer advertises.
            out["adapters"] = self._registry.stats()
            out["adapters"]["loaded"] = self._registry.loaded()
        return out

    def close(self, drain_s: float = 10.0) -> None:
        """Deterministic shutdown: refuse new work, give in-flight
        requests ``drain_s`` to finish, fail whatever remains with
        BatcherClosed, and join the loop thread (bounded — mirrors
        ModelServer.stop(); no background-thread leakage across a test
        session)."""
        with self._lock:
            if self._stopped:
                self._work.notify_all()
            else:
                self._stopped = True
                self._drain_deadline = faults.monotonic() \
                    + max(0.0, drain_s)
                self._work.notify_all()
        self._thread.join(timeout=max(5.0, drain_s + 5.0))
        # The prefix index dies with the engine (reload invalidation:
        # the serving layer rebuilds engine + pool per model version);
        # clear it here too so a closed-but-referenced engine can never
        # serve a stale prefix.  After a clean drain every slot has
        # released its pages, so dropping the cached records frees the
        # whole pool.
        with self._lock:
            self._mgr.invalidate()
        # A closed engine exports no live slots, queue, or resident
        # KV: hot-swap retires the metric series at zero instead of
        # freezing a stale occupancy in /metrics forever.
        self._set_occ_gauge(0)
        self._set_queue_gauge(0)
        self._kv_blocks_gauge.set(0, engine=self._metric_name)
        self._set_kv_used_gauge(0)
        self._kv_spilled_gauge.set(0, engine=self._metric_name)
        self._kv_spilled_last = 0
        self._host_tier_gauge.set(0, engine=self._metric_name)
        self._mesh_gauge.set(0, engine=self._metric_name)

    def _mesh_devices(self) -> int:
        from kubeflow_tpu.serving.sharding import mesh_devices

        return mesh_devices(self.mesh)

    # -- step loop --------------------------------------------------------

    def _phase(self, name: str, **facts) -> _Phase:
        """``with self._phase("drain"):`` — loop thread only."""
        return _Phase(self, name, facts)

    def _round_read(self, handed: int) -> _ReadPhase:
        """``with self._round_read(handed):`` inside ``round_wait``,
        right after the read the round makes first; ``handed`` is the
        number ``_device_has_work`` gave the call that was read."""
        return _ReadPhase(self, handed)

    def _device_has_work(self, phase: _Phase) -> int:
        """A call that hands the device work (a round's or a chunk's
        program) has just returned; the calls are numbered, and this
        one's number is returned.  The open turnaround, if any, ends
        here.  It began when the first result
        of the last such call landed with nothing queued behind it
        (``_ReadPhase``), the earliest moment the loop could know the
        device had nothing left to do: the stretch is the host's
        critical path of a round the loop did not get ahead of.  Summed
        and counted in ``stats()``, and a fact of the dispatching
        ``phase`` so that a traced run shows it beside the device's
        gap."""
        # Loop-thread-owned, as _ready_at below.
        # kft: allow=lock-guard
        self._handed += 1
        ready = self._ready_at
        if ready is not None:
            # Loop-thread-owned; wait_work forgets it under the lock
            # only because that is where the loop finds out it has gone
            # idle.
            # kft: allow=lock-guard
            self._ready_at = None
            took = time.perf_counter() - ready
            counters = self._counters  # loop-thread-owned keys, one writer
            counters["turnaround_s_sum"] += took
            counters["turnarounds"] += 1
            phase.facts(since_ready_us=int(took * 1e6))
        return self._handed

    def _aot(self, fn, *args, **static):
        """``fn.lower(*args).compile()`` for every AOT program of the
        engine, with the wall time it took added to ``compile_s`` and
        the compiler's own account of the program's memory kept as
        ``compiled_peak_bytes`` (the largest over the programs)."""
        t0 = time.perf_counter()
        compiled = fn.lower(*args, **static).compile()
        dt = time.perf_counter() - t0
        mem = compiled.memory_analysis()
        peak = 0 if mem is None else int(
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        with self._lock:
            self._counters["compile_s"] += dt
            self._counters["compiled_peak_bytes"] = max(
                self._counters["compiled_peak_bytes"], peak)
        self._compile_ctr.inc(dt, engine=self._metric_name)
        return compiled

    def _push_loop_seconds(self) -> None:
        """The phase sums, to the registry ``/metrics`` serves: once
        an iteration, in its closing ``account``, each phase's growth
        since the last push.  Together they are the iteration's own
        phase times (that closing ``account`` itself counts with the
        next iteration), which ``_note_iteration`` holds against the
        running mean."""
        own = {}
        for phase, key in _PHASE_KEY.items():
            total = self._counters[key]
            own[phase] = total - self._loop_pushed[key]
            if own[phase] > 0:
                self._loop_ctr.inc(own[phase],
                                   engine=self._metric_name, phase=phase)
                self._loop_pushed[key] = total
        self._note_iteration(own)

    def _note_iteration(self, own: Dict[str, float]) -> None:
        """The slow iteration, kept: one whose wall time (``wait_work``
        left out) passes ``_SLOW_ROUND_FACTOR`` times the running mean
        adds 1 to ``slow_rounds`` and what it took over the mean to
        ``slow_round_s_sum``, and is logged, at most once a second, with
        its own time in every phase and the facts of its phases: a 2 s
        round among 5 ms ones names its phase in the run it happens in.
        Neither held against the mean nor part of it: an iteration that
        compiled a program, and one that neither dispatched a round nor
        waited for the device (a chunk dispatched with no slot live costs
        the host's 2 ms, and the iteration that then reads the last
        chunk's token waits for all of them: on the chip every prefilled
        context of a set-up read as a slow iteration while those
        counted).  The round that opens a busy stretch is dispatched and
        left unread (``_dispatch_round``): its iteration waits for
        nothing and counts."""
        counters = self._counters  # loop-thread-owned keys, one writer
        if counters["compile_s"] != self._compile_seen:
            self._compile_seen = counters["compile_s"]
            return
        if not own["round_wait"] and not own["round_dispatch"]:
            return
        took = sum(own.values()) - own["wait_work"]
        mean = self._iter_mean
        limit = _SLOW_ROUND_FACTOR * mean
        if took > limit and self._iter_samples:
            counters["slow_rounds"] += 1
            counters["slow_round_s_sum"] += took - mean
            now = time.perf_counter()
            if now - self._slow_logged_at >= 1.0:
                self._slow_logged_at = now
                facts = self._iter_facts
                log.warning(
                    "engine %r: slow iteration %d took %.4f s against a "
                    "mean of %.4f s, most of it in %s; own seconds by "
                    "phase: %s; width=%s steps=%s live=%s admitted=%s "
                    "chunks=%s", self._metric_name,
                    counters["loop_rounds"], took, mean,
                    max((p for p in own if p != "wait_work"), key=own.get),
                    " ".join(f"{p}={own[p]:.4f}" for p in _PHASES),
                    *(facts.get(k, "-") for k in (
                        "width", "steps", "live", "admitted", "chunks")))
            took = limit
        self._iter_samples = min(self._iter_samples + 1,
                                 _ITER_MEAN_SAMPLES)
        self._iter_mean = mean + (took - mean) / self._iter_samples

    def _free_slots_locked(self) -> List[int]:
        return [i for i, r in enumerate(self._slot_req) if r is None]

    def _fair_pick_locked(self) -> int:
        """Per-tenant fair admission (§5.11): among the queued
        requests, pick the one whose adapter key ("" = base traffic)
        was admitted least recently, oldest-first within a tenant —
        a hot adapter's burst cannot starve co-batched neighbors,
        because every other tenant's queue head outranks the hot
        tenant's next request.  Pure FIFO when nothing queued names an
        adapter, so single-tenant engines keep the exact pre-adapter
        admission order.  The caller still stops on the first
        unplannable pick, which preserves the no-starvation property
        under pool pressure: a waiting request is never jumped
        indefinitely."""
        if self._registry is None or len(self._queue) < 2:
            return 0
        if all(e.get("adapter_name") is None for e in self._queue):
            return 0
        best, best_key = 0, None
        for i, e in enumerate(self._queue):
            key = (self._fair_last.get(
                e.get("adapter_name") or "", -1), i)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def _apply_adapter_updates(self) -> None:
        """Hot adapter load/evict, device side (loop thread, between
        program calls): swap the registry's current stacked delta
        arrays into the param tree when its version moved.  The stack
        has identical shapes/dtypes on every version (rows mutate,
        geometry never does), so the swap NEVER recompiles a program;
        device_put preserves each leaf's existing placement, so under
        a mesh the stacked axis lands exactly where the compiled SPMD
        programs expect it.  Copy-on-write on the registry side means
        in-flight dispatches keep reading the old leaves — a program
        never observes a torn row."""
        import jax

        stack, version = self._registry.stack_snapshot()
        if version == self._adapter_version:
            return

        def place(new, old):
            sharding = getattr(old, "sharding", None)
            return jax.device_put(np.asarray(new), sharding) \
                if sharding is not None else np.asarray(new)

        params = dict(self.params)
        params["adapters"] = jax.tree_util.tree_map(
            place, stack, dict(self.params["adapters"]))
        self.params = params
        self._adapter_version = version

    def _sweep_expired_locked(self) -> List[dict]:
        """Pull every deadline-expired request out of the queue AND the
        live slot table (caller fails them outside the lock).

        In-flight expiry rides the deterministic-retirement path: the
        slot is freed NOW — the next admission's first prefill chunk
        freezes it on device, which is the device-side abort — and a
        first token of the request still in _pending is dropped by
        _drain_one's event-set check.  No other slot's state is
        touched, so co-resident generations are unaffected.  The
        sweep runs between dispatches, with up to one round unread
        (``_dispatch_round``), so expiry granularity is two rounds
        (``_round_width`` keeps each under the tightest deadline):
        tokens that complete a request before the next sweep are
        delivered, and an unread round's tokens for an expired request
        are dropped at its drain."""
        pnow = faults.monotonic()
        expired: List[dict] = []
        live = []
        for entry in self._queue:
            d = entry["deadline"]
            if d is not None and d <= pnow:
                expired.append(entry)
            else:
                live.append(entry)
        if len(live) != len(self._queue):
            self._queue[:] = live
            self._set_queue_gauge(len(self._queue))
        for i, entry in enumerate(self._slot_req):
            if entry is None:
                continue
            d = entry["deadline"]
            if d is not None and d <= pnow:
                self._slot_req[i] = None
                # Park the dead occupant's table row: its in-flight
                # device state (done may still be False) keeps
                # advancing harmlessly, but every write now drops —
                # its freed pages can be reallocated immediately.
                self._tables[i][:] = self.kv_pool_blocks
                self._tables_dirty = True
                self._release_entry_locked(entry)
                self._counters["in_flight"] -= 1
                expired.append(entry)
        if expired:
            self._counters["expired"] += len(expired)
        return expired

    def _fail_expired(self, expired: List[dict]) -> None:
        if not expired:
            return
        self._expired_ctr.inc(len(expired), batcher=self._metric_name)
        for entry in expired:
            # Queue-expired entries never reach _release_entry_locked
            # (they hold no pages) — unpin their adapters here.
            self._unpin_adapter(entry)
        for entry in expired:
            if not entry["event"].is_set():
                if entry["trace"] is not None:
                    tracing.record_span(
                        "engine.request", entry["trace"],
                        entry["t_perf"], time.perf_counter(),
                        status="deadline_expired",
                        attrs={"engine": self._metric_name,
                               "emitted": len(entry["emitted"]),
                               "budget": entry["new"]})
                entry["err"] = DeadlineExceeded(
                    f"deadline expired after {len(entry['emitted'])} "
                    f"of {entry['new']} tokens "
                    f"(engine {self._metric_name!r})")
                entry["event"].set()

    def _unpin_adapter(self, entry: dict) -> None:
        """Drop an entry's adapter pin (idempotent — the pin travels
        as a pop-once key).  Every terminal path calls this: release,
        expiry, queue failure, abort, and the typed admission sheds —
        so an adapter row is LRU-evictable exactly when no live
        request references it."""
        pin = entry.pop("adapter_pin", None)
        if pin is not None and self._registry is not None:
            self._registry.release(pin)

    def _release_entry_locked(self, entry: dict) -> None:
        """Return an entry's physical pages (slot refs) and never-taken
        reservation to the pool.  Pages a published prefix record
        advertises stay resident as evictable cache.  Idempotent —
        retirement, expiry, and drain can each reach a request once.
        Never touches the slot's table row: by release time the row
        may already belong to a successor request."""
        self._unpin_adapter(entry)
        if entry["released"]:
            return
        entry["released"] = True
        self._mgr.release(entry["blocks"], unreserve=entry["res_left"])
        entry["blocks"] = []
        entry["res_left"] = 0

    def _plan_blocks_locked(self, entry: dict):
        """Reserve the entry's worst-case page count (aliasing the
        longest cached prefix for free); None = the pool cannot cover
        it yet, leave the request at the queue head — retirements free
        pages, and FIFO order means a starving big request is never
        jumped into starvation.  A request carrying a KV-handoff
        payload skips the local prefix lookup (limit 0): its pages
        arrive from the prefill tier and land in PRIVATE blocks, so
        the whole worst case reserves.

        Hierarchical KV (§5.10): when the HOST tier covers more of the
        prompt than the device index, the admission plans like a
        handoff import instead — full private reservation, spilled
        pages re-imported through the same ``kv_import`` program — so
        a spilled session resumes without re-prefilling what the tier
        preserved."""
        prompt = entry["tokens"][0]
        salt = entry.get("adapter_salt", b"")
        limit = 0 if entry.get("handoff") else int(prompt.shape[0]) - 1
        spill_in = None
        if limit > 0 and self.host_spill_blocks:
            payload, depth = self._mgr.lookup_spilled(
                prompt, limit, salt=salt)
            if payload is not None and depth * self.kv_block_tokens \
                    > self._mgr.peek(prompt, limit, salt=salt):
                spill_in = (payload, depth)
                limit = 0
        plan = self._mgr.admit(prompt, limit, entry["res_blocks"],
                               salt=salt)
        if plan is not None:
            entry["spill_in"] = spill_in
        return plan

    # -- disaggregated prefill/decode handoff -----------------------------

    def _parse_handoff(self, payload, length: int):
        """Validate + normalize a KV-handoff payload against THIS
        engine's pool geometry; returns {"covered", "k", "v"} (pages
        trimmed to the full blocks covering at most ``length - 1``
        positions — at least one prompt token always recomputes
        locally, which is what arms the slot's scalars through the
        ordinary final prefill chunk), or None when there is nothing
        importable.  Raises ValueError on a geometry/dtype mismatch —
        a payload from a differently-configured prefill replica must
        answer 400, not corrupt the pool."""
        if payload is None:
            return None
        if not isinstance(payload, dict):
            raise ValueError("kv_handoff must be an object")
        bt = int(payload.get("block_tokens", 0))
        if bt != self.kv_block_tokens:
            raise ValueError(
                f"kv_handoff block_tokens {bt} != engine page size "
                f"{self.kv_block_tokens}")
        int8 = self.decode.kv_cache_dtype == "int8"
        page_shape = (self.cfg.kv_planes, self.kv_block_tokens,
                      self.cfg.n_kv_heads, self.cfg.head_dim)

        def norm(side, raw):
            if int8:
                if not isinstance(raw, dict) or "values" not in raw \
                        or "scale" not in raw:
                    raise ValueError(
                        f"kv_handoff {side}: engine pool is int8 — "
                        f"payload needs values + scale")
                vals = np.asarray(raw["values"], np.int8)
                scale = np.asarray(raw["scale"], np.float32)
                if scale.shape != vals.shape[:-1]:
                    raise ValueError(
                        f"kv_handoff {side}: scale {scale.shape} "
                        f"must match values {vals.shape} minus the "
                        f"trailing dim")
                return vals, scale
            if isinstance(raw, dict):
                raise ValueError(
                    f"kv_handoff {side}: engine pool is "
                    f"{self.cfg.dtype} — got a quantized payload")
            return np.asarray(raw), None

        k_vals, k_scale = norm("k", payload.get("k"))
        v_vals, v_scale = norm("v", payload.get("v"))
        for side, vals in (("k", k_vals), ("v", v_vals)):
            if vals.ndim != 5 or (vals.shape[0],) + vals.shape[2:] \
                    != page_shape:
                raise ValueError(
                    f"kv_handoff {side} pages {vals.shape} do not "
                    f"match pool pages [planes={page_shape[0]}, n, "
                    f"block_tokens={page_shape[1]}, "
                    f"hkv={page_shape[2]}, d={page_shape[3]}]")
        if k_vals.shape[1] != v_vals.shape[1]:
            raise ValueError("kv_handoff k/v page counts differ")
        n = min(int(k_vals.shape[1]),
                (int(length) - 1) // self.kv_block_tokens)
        if n <= 0:
            return None
        return {
            "covered": n * self.kv_block_tokens,
            "k": (k_vals[:, :n], None if k_scale is None
                  else k_scale[:, :n]),
            "v": (v_vals[:, :n], None if v_scale is None
                  else v_scale[:, :n]),
        }

    def _pad_pages(self, pages, span: int):
        """Page stack [L, n, bt, hkv(, d)] -> the import program's
        static [L, span, ...] shape (zero padding rides sentinel ids
        and drops on device)."""
        from kubeflow_tpu.ops.quantize import QTensor

        vals, scale = pages
        n = vals.shape[1]
        dtype = (self.cfg.dtype if scale is None else np.int8)
        pad = np.zeros(
            (vals.shape[0], span) + vals.shape[2:], dtype)
        pad[:, :n] = vals
        if scale is None:
            return pad
        pad_s = np.zeros(
            (scale.shape[0], span) + scale.shape[2:], np.float32)
        pad_s[:, :n] = scale
        return QTensor(pad, pad_s, (-1,))

    def _import_pages(self, entry: dict, pages: dict) -> int:
        """Shared page-import tail (loop thread, slot claimed): take
        the covered pages from the entry's reservation, scatter the
        page data into them (ONE kv_import program call — the
        transfer unit is a block-page list, never a contiguous slot
        region), and set the chunk-prefill offset past them — from
        there the request is indistinguishable from a local
        prefix-cache resume, which is what makes both handoff import
        (§5.9) and host-tier re-import (§5.10) token-identical to
        local prefill at every chunk boundary.  ``pages`` is the
        normalized {"covered", "k", "v"} form."""
        from kubeflow_tpu.models.generate import import_kv_pages

        self._ensure_cover(entry, pages["covered"] - 1)
        n = pages["covered"] // self.kv_block_tokens
        span = self._table_blocks
        ids = np.full((span,), self.kv_pool_blocks, np.int32)
        ids[:n] = entry["blocks"][:n]
        pages_k = self._pad_pages(pages["k"], span)
        pages_v = self._pad_pages(pages["v"], span)
        if self._import_exec is None:
            self._import_exec = self._aot(
                import_kv_pages, self._state, pages_k, pages_v, ids)
        self._state = self._import_exec(
            self._state, pages_k, pages_v, ids)
        entry["pos"] = pages["covered"]
        return n

    def _import_handoff(self, entry: dict) -> None:
        """Admission, handoff side: scatter the prefill tier's
        transferred pages into the reserved blocks and start chunked
        prefill at the covered offset."""
        # Chaos hook: the decode-tier import path (sleep = slow
        # cross-replica transfer, raise = import failure — the router
        # surfaces it rather than hanging the tiered dispatch).
        faults.fire("engine.kv_handoff")
        n = self._import_pages(entry, entry["handoff"])
        with self._lock:
            self._counters["handoff_pages_in"] += n
        self._handoff_ctr.inc(n, engine=self._metric_name,
                              direction="import")

    def _import_spill(self, entry: dict) -> None:
        """Admission, host-tier side (§5.10): re-import the spilled
        pages the plan matched, through the same kv_import program a
        disaggregated handoff uses — re-admitting a spilled session
        costs one page scatter plus the uncovered suffix's chunks,
        never a full re-prefill.  A fault here sheds THIS admission
        typed 429 (the caller releases its pages; the host record is
        untouched, so no page leaks in either tier) instead of killing
        the engine: losing one admission to a sick spill tier is
        degradation, not death."""
        payload, depth = entry.pop("spill_in")
        try:
            # Chaos hook: the spill-in import path (raise = spill-tier
            # failure mid-admission -> typed 429; sleep = slow host
            # copy).
            faults.fire("engine.spill")
        except Exception as exc:
            raise _SpillShed(str(exc)) from exc
        (k_vals, k_scale) = payload["k"]
        (v_vals, v_scale) = payload["v"]
        pages = {
            "covered": depth * self.kv_block_tokens,
            "k": (k_vals[:, :depth], None if k_scale is None
                  else k_scale[:, :depth]),
            "v": (v_vals[:, :depth], None if v_scale is None
                  else v_scale[:, :depth]),
        }
        n = self._import_pages(entry, pages)
        with self._lock:
            self._counters["spill_pages_in"] += n
            self._mgr.spills_in += n
        self._kv_spill_ctr.inc(n, engine=self._metric_name,
                               direction="in")

    def _attach_export(self, entry: dict) -> None:
        """Delivery, prefill side (loop thread, pages still held):
        gather the finished full-block prompt pages off the pool into
        the response payload — the same normalized form
        ``kv_handoff`` imports, so prefill and decode tiers stay
        wire-symmetric.  Runs before release: the pages are still
        slot-referenced, so nothing can overwrite them mid-gather."""
        from kubeflow_tpu.models.generate import gather_kv_pages

        true_len = int(entry["tokens"].shape[1])
        n = min((true_len - 1) // self.kv_block_tokens,
                len(entry["blocks"]))
        if n <= 0:
            return
        # Chaos hook: the prefill-tier export path (raise = export
        # failure at delivery; the router's tiered dispatch falls back
        # to the untiered path).
        faults.fire("engine.kv_handoff")
        pages_k, pages_v = gather_kv_pages(
            self._state, entry["blocks"][:n])

        def wire(pages):
            vals, scale = pages
            return vals if scale is None \
                else {"values": vals, "scale": scale}

        entry["out"]["kv_handoff"] = {
            "block_tokens": self.kv_block_tokens,
            "tokens_covered": n * self.kv_block_tokens,
            "k": wire(pages_k),
            "v": wire(pages_v),
        }
        with self._lock:
            self._counters["handoff_pages_out"] += n
        self._handoff_ctr.inc(n, engine=self._metric_name,
                              direction="export")

    def _ensure_cover(self, entry: dict, upto_pos: int) -> None:
        """Grow the slot's block table to cover position ``upto_pos``,
        taking physical pages from the entry's admission reservation
        (capped there — positions past the reservation park on the
        table sentinel and their writes drop; only positions the
        frontier can never reach land there)."""
        target = min(upto_pos // self.kv_block_tokens + 1,
                     entry["res_blocks"])
        if target <= len(entry["blocks"]):
            return
        # Chaos hook: raise = allocation failure (engine death at the
        # growth site — _abort resolves every waiter), sleep = slow
        # allocator under pool pressure.
        faults.fire("engine.alloc_block")
        row = self._tables[entry["slot"]]
        with self._lock:
            while len(entry["blocks"]) < target:
                blk = self._mgr.take()
                row[len(entry["blocks"])] = blk
                entry["blocks"].append(blk)
                entry["res_left"] -= 1
            rec_d, blk_d = self._flush_evictions_locked()
            self._tables_dirty = True
        if rec_d:
            self._evict_ctr.inc(rec_d, engine=self._metric_name)
        if blk_d:
            self._kv_evict_ctr.inc(blk_d, engine=self._metric_name)

    def _flush_evictions_locked(self):
        """Fold the manager's eviction totals into the engine counters;
        returns the (records, blocks) deltas for the prom counters."""
        rec_d = self._mgr.evictions - self._evict_rec_seen
        blk_d = self._mgr.block_evictions - self._evict_blk_seen
        if rec_d:
            self._evict_rec_seen = self._mgr.evictions
            self._counters["prefix_evictions"] += rec_d
        if blk_d:
            self._evict_blk_seen = self._mgr.block_evictions
            self._counters["kv_evictions"] += blk_d
        return rec_d, blk_d

    def _set_queue_gauge(self, depth: int) -> None:
        if depth != self._queue_last:
            self._queue_last = depth
            self._queue_gauge.set(depth, engine=self._metric_name)

    def _set_occ_gauge(self, active: int) -> None:
        if active != self._occ_last:
            self._occ_last = active
            self._occ_gauge.set(active, engine=self._metric_name)

    def _set_kv_used_gauge(self, used: int) -> None:
        if used != self._kv_used_last:
            self._kv_used_last = used
            self._kv_used_gauge.set(used, engine=self._metric_name)

    def _set_kv_spilled_gauge(self, spilled: int) -> None:
        if spilled != self._kv_spilled_last:
            self._kv_spilled_last = spilled
            self._kv_spilled_gauge.set(spilled, engine=self._metric_name)

    # -- host spill tier (§5.10) ------------------------------------------

    def _spill_tick(self, max_records: int = 4) -> int:
        """Evacuate LRU-cold idle records to the host tier while
        take() pressure would otherwise destroy-evict them (loop
        thread, between program calls — the pool buffers are donated
        to the step programs, so nobody else may gather them).  Each
        spill is select-under-lock, gather-OUTSIDE-the-lock (a device
        read must never run under the engine lock), complete-under-
        lock; spill() revalidates the candidate, so the off-lock
        window is race-free.  A gather fault leaves the record
        resident — destructive LRU eviction remains the fallback and
        correctness is unharmed.  Returns records spilled."""
        from kubeflow_tpu.models.generate import gather_kv_pages

        spilled = 0
        while spilled < max_records and self._mgr.spill_pressure() > 0:
            with self._lock:
                cands = self._mgr.spill_candidates(1)
            if not cands:
                break
            rec = cands[0]
            n = len(rec.blocks)
            with self._lock:
                # Gather-free fast path: a parked session's chain is
                # already host-resident (host_put at delivery), so its
                # device pages can drop without re-copying them.
                freed = self._mgr.spill(rec, None)
                if freed is not None:
                    self._counters["spill_pages_out"] += n
            if freed is not None:
                self._kv_spill_ctr.inc(n, engine=self._metric_name,
                                       direction="out")
                spilled += 1
                continue
            try:
                # Chaos hook: the spill-out gather (raise = gather
                # failure — the record stays resident and eviction
                # falls back to destroying it; sleep = slow host copy).
                faults.fire("engine.spill")
                pages_k, pages_v = gather_kv_pages(
                    self._state, rec.blocks)
            except Exception:
                break
            with self._lock:
                freed = self._mgr.spill(
                    rec, {"k": pages_k, "v": pages_v})
                if freed is None:
                    continue  # went stale off-lock; reselect
                self._counters["spill_pages_out"] += n
            self._kv_spill_ctr.inc(n, engine=self._metric_name,
                                   direction="out")
            spilled += 1
        self._set_kv_spilled_gauge(
            self._mgr.host_used_blocks())
        return spilled

    def _shed_admitted(self, entry: dict, slot: int, why: str) -> None:
        """Shed one ALREADY-CLAIMED admission typed 429 (spill-tier
        fault mid-admission): release its pages and reservation, free
        the slot (no chunk was dispatched, so the previous occupant's
        claim-time freeze still holds), and resolve the waiter.  The
        host tier is untouched — its record still serves the next
        attempt."""
        with self._lock:
            if self._slot_req[slot] is entry:
                self._slot_req[slot] = None
            self._tables[slot][:] = self.kv_pool_blocks
            self._tables_dirty = True
            self._release_entry_locked(entry)
            self._counters["in_flight"] -= 1
            self._counters["shed"] += 1
            self._counters["kv_shed_no_blocks"] += 1
        self._shed_ctr.inc(batcher=self._metric_name)
        self._kv_shed_ctr.inc(engine=self._metric_name)
        entry["err"] = Overloaded(
            f"engine {self._metric_name!r} spill-tier re-import "
            f"failed mid-admission: {why}",
            retry_after_s=self.overload_retry_after_s)
        entry["event"].set()

    def _park_kv(self, entry: dict) -> None:
        """Delivery-side session park (§5.10, loop thread, pages still
        slot-held): publish the FULL context — prompt + emitted; the
        last sampled token has no cache entry — as an ordinary device
        record AND eagerly copy its full-block pages into the host
        tier.  A parked conversation is cold by definition: the next
        turn resumes through the device index while the record is
        warm, through host-tier re-import once pressure spills it,
        and over :fetch_kv from a surviving peer after failover.  A
        gather fault degrades to device-resident-only parking."""
        from kubeflow_tpu.models.generate import gather_kv_pages

        context = np.concatenate(
            [entry["tokens"][0],
             np.asarray(entry["emitted"], np.int32)])
        true_len = int(context.shape[0]) - 1
        n = min(true_len // self.kv_block_tokens, len(entry["blocks"]))
        salt = entry.get("adapter_salt", b"")
        with self._lock:
            self._counters["parked_sessions"] += 1
            if n > 0 and self.prefix_caching:
                self._mgr.publish(context, true_len, entry["blocks"],
                                  salt=salt)
        if n <= 0 or not self.host_spill_blocks:
            return
        try:
            # Chaos hook: the park-side gather — same site and same
            # degradation as the pressure spill above.
            faults.fire("engine.spill")
            pages_k, pages_v = gather_kv_pages(
                self._state, entry["blocks"][:n])
        except Exception:
            return
        with self._lock:
            stored = self._mgr.host_put(
                context, true_len, {"k": pages_k, "v": pages_v},
                salt=salt)
            if stored:
                self._counters["spill_pages_out"] += stored
        if stored:
            self._kv_spill_ctr.inc(stored, engine=self._metric_name,
                                   direction="out")
        self._set_kv_spilled_gauge(
            self._mgr.host_used_blocks())

    def fetch_kv(self, inputs: Dict[str, Any]) -> Dict[str, Any]:
        """Fleet-wide session fetch (§5.10, any thread): serve the
        longest HOST-TIER match of ``tokens`` in the export wire form
        (``{"kv_handoff", "tokens_covered"}`` — encode_kv_handoff
        makes it portable), or a miss with no payload.  Host tier
        ONLY, by design: the device pool's buffers are donated to
        in-flight step programs, so a transport thread must never
        gather them — and parked/spilled sessions, the only state a
        failover survivor needs, are host-resident by construction."""
        tokens = np.asarray(inputs["tokens"], np.int32).reshape(-1)
        # Adapter-scoped lookup: a variant's digest chain is salted
        # with its CONTENT digest, so a fetching peer passes the same
        # digest to address the same pages (base traffic: no salt).
        salt = b""
        digest = inputs.get("adapter_digest")
        if digest:
            salt = bytes.fromhex(str(digest))
        # Chaos hook: the cross-replica fetch path (raise = fetch
        # failure — the router falls back to recompute-resume; sleep =
        # slow fetch).
        faults.fire("engine.fetch")
        with self._lock:
            self._counters["fetches"] += 1
            payload, depth = self._mgr.lookup_spilled(
                tokens, int(tokens.shape[0]), salt=salt)
        if payload is None:
            return {"kv_handoff": None, "tokens_covered": 0}

        def side(pages):
            vals, scale = pages
            if scale is None:
                return vals[:, :depth]
            return {"values": vals[:, :depth],
                    "scale": scale[:, :depth]}

        covered = depth * self.kv_block_tokens
        return {
            "kv_handoff": {
                "block_tokens": self.kv_block_tokens,
                "tokens_covered": covered,
                "k": side(payload["k"]),
                "v": side(payload["v"]),
            },
            "tokens_covered": covered,
        }

    def _begin_prefill(self, entry: dict, slot: int) -> None:
        """Admission, host side.  The admission plan already aliased
        the longest cached prefix into the slot's block table (a
        refcount bump — no device copy exists), so all that remains is
        accounting and the FIRST prefill chunk, dispatched at claim
        time: its unconditional device-side ``done`` freeze is what
        makes reusing a deadline-expired slot safe — without it an
        interleaved decode round would advance the dead occupant and
        scatter through the NEW request's table."""
        prompt = entry["tokens"][0]
        true_len = int(prompt.shape[0])
        cached = entry["cached"]
        # Chaos hook: sleep = slow admission; raise = device death at
        # admission (propagates to _abort, every waiter resolved).
        faults.fire("engine.admit")
        # Queue wait (submit -> slot claim), stamped once: the sum
        # every request feeds and the admission span of a traced one.
        claimed = entry["t_claim_perf"] = time.perf_counter()
        waited = claimed - entry["t_perf"]
        with self._lock:
            self._counters["queue_wait_s_sum"] += waited
            self._counters["admitted"] += 1
            self._counters["prompt_tokens"] += true_len
            if self.prefix_caching:
                # Hit/miss accounting only when caching is ON — with
                # caching disabled a climbing miss counter would read
                # as "cache enabled and failing" on dashboards.
                if cached:
                    self._counters["prefix_hits"] += 1
                    self._counters["cached_tokens"] += cached
                else:
                    self._counters["prefix_misses"] += 1
        self._queue_wait_ctr.inc(waited, engine=self._metric_name)
        if self.prefix_caching:
            (self._hits_ctr if cached else self._misses_ctr).inc(
                engine=self._metric_name)
        if entry["trace"] is not None:
            # Admission span: queue wait (submit -> slot claim) plus
            # the prefix verdict — TTFT debugging's first question
            # ("was it queued or was it prefill?") answered per
            # request.  cached tokens cost zero copies now, so there
            # is no copy_ms to report.
            tracing.record_span(
                "engine.admission", entry["trace"], entry["t_perf"],
                claimed,
                attrs={"engine": self._metric_name, "slot": slot,
                       "prompt_tokens": true_len,
                       "cached_tokens": cached,
                       "prefix": "hit" if cached else "miss"})
        if entry.get("handoff"):
            # Disaggregated decode tier: scatter the prefill tier's
            # transferred pages into the reserved blocks, then chunk-
            # prefill only the uncovered suffix (>= 1 token — the
            # final chunk arms the slot exactly as a local prefill
            # would).
            self._import_handoff(entry)
        elif entry.get("spill_in"):
            # Host-tier re-import (§5.10): same mechanics, pages from
            # this replica's own spill tier instead of the wire.
            self._import_spill(entry)
        entry["prefilling"] = True
        self._prefill_chunk(entry)  # claim-time freeze + first chunk
        if entry["prefilling"]:
            self._prefilling.append(entry)

    def _sparse_reads(self, slots, steps):
        """What a round's ``steps`` decode steps read of the index and
        window planes, from ``(positions its first step sees, tokens
        emitted)`` a slot (the step's own position counted, as in
        ``attended``): a step that sees l positions scores l index keys
        a full plane, attends ``index_topk`` of them at most, and reads
        the last ``window`` positions a sliding plane.  To score the l
        keys the program READS l rounded up to whole pages where it
        walks the slot's own pages (``_index_walk``), else, for every
        row of the call, the key tiles up to the longest slot's."""
        from kubeflow_tpu.models.generate import index_positions_scored

        slots = list(slots)
        scored = chosen = window = read = 0
        topk, span = self.cfg.index_topk, self.cfg.window
        pages, bt = self._tables.shape[1], self.kv_block_tokens
        for at, n in slots:
            scored += n * at + n * (n - 1) // 2
            chosen += sum(min(at + j, topk) for j in range(n))
            window += sum(min(at + j, span) for j in range(n))
            if self._index_walk:
                read += sum(-(-(at + j) // bt) * bt for j in range(n))
        if self._index_planes and not self._index_walk:
            # A slot that stopped keeps its length and rides along.
            read = self.slots * sum(
                index_positions_scored(
                    pages, bt, self.slots * self.cfg.index_heads,
                    max(at + min(j, n) for at, n in slots))
                for j in range(steps))
        return dict(zip(_SPARSE_KEYS, (
            scored * self._index_planes, chosen * self._index_planes,
            window * self.cfg.window_planes, read * self._index_planes)))

    def _prefill_chunk(self, entry: dict) -> None:
        """One static-width chunk of one entry's prompt into its slot
        (dispatch only — the final chunk's first sampled token joins
        the lagged pending stream).  The program call returns when the
        chunk is ENQUEUED, so what is timed here (``_chunk_times``,
        ``stats()["prefill_chunk_p95_ms"]``, the ``engine.prefill_chunk``
        span) is the host's dispatch, not the chunk's compute: the
        device runs the chunk ahead of the next round, whose wait
        (``round_wait``, and ``busy_s`` through the round's timing)
        covers it, and ``prefill_span_s_sum`` holds a request's whole
        prefill from slot claim to first token."""
        from kubeflow_tpu.models.generate import (
            index_positions_scored,
            prefill_chunk_into_slot,
            view_positions_scored,
        )

        w = self.chunk_w
        prompt = entry["tokens"][0]
        true_len = int(prompt.shape[0])
        # The chunk's [start, start+w) window may overhang the
        # reserved pages on the final chunk (right-pad columns past
        # the prompt): the paged scatter PARKS those positions on the
        # table sentinel and drops them — they sit beyond every
        # frontier the slot can reach, so no pull-back dance is
        # needed.
        start = entry["pos"]
        chunk = np.zeros((1, w), np.int32)
        seg = prompt[start:start + w]
        chunk[0, :seg.shape[0]] = seg
        # A drafting model's final chunk also writes its draft layer's
        # row at the prompt's length, one past the chunk at most.
        self._ensure_cover(entry, start + w - 1 + self._mtp)
        if self._chunk_exec is None:
            lower_args = [
                self.cfg, self.params, self._state, self.decode,
                chunk, np.int32(0), np.int32(1), np.int32(1),
                np.int32(0), np.int32(0), self._tables[:1]]
            if self._registry is not None:
                # Adapter-array serving: the row index is a TRACED
                # operand of the ONE chunked-prefill executable (row 0
                # = base), so compiled_programs() never grows a
                # per-adapter entry.
                lower_args.append(np.int32(0))
            if self._mtp:
                lower_args += [None, np.int32(-1)]
            self._chunk_exec = self._aot(
                prefill_chunk_into_slot, *lower_args,
                grouped_kernel=self._grouped_kernel)
        call_args = [
            self.params, self._state, chunk,
            np.int32(start), np.int32(true_len), np.int32(entry["new"]),
            np.int32(entry["slot"]), np.int32(entry["seed"]),
            self._tables[entry["slot"]:entry["slot"] + 1].copy()]
        if self._registry is not None:
            call_args.append(np.int32(entry.get("adapter", 0)))
        if self._mtp:
            # The slot's first chunk after a prefix hit: nothing ran in
            # this slot before it, and the draft layer's first row reads
            # the stream one position back (prefill_chunk_into_slot).
            call_args += [None, np.int32(
                prompt[start - 1] if start and start == entry["cached"]
                else -1)]
        t0 = time.perf_counter()
        self._state, tok = self._chunk_exec(*call_args)
        dt = time.perf_counter() - t0
        handed = self._device_has_work(self._phases[-1])
        entry["pos"] = start + w
        finished = entry["pos"] >= true_len
        if finished:
            entry["prefilling"] = False
            entry["scheduled"] = 1
            self._pending.append((tok, [(0, entry)], None, handed))
            if self.prefix_caching:
                # Publication is free: the full-block prefix pages
                # this prefill just wrote ARE the cache entry — a
                # refcount bump in the index, no donor copy.
                with self._lock:
                    self._mgr.publish(
                        prompt, true_len, entry["blocks"],
                        salt=entry.get("adapter_salt", b""))
        # An indexer's chunk scores its slot's index keys by tiles of
        # their own; the attention then reads the chosen rows alone.
        scored = index_positions_scored(
            self._tables.shape[1], self.kv_block_tokens,
            w * self.cfg.index_heads, start + w) if self.cfg.indexed \
            else view_positions_scored(
                self._tables.shape[1], self.kv_block_tokens, w, start + w)
        with self._lock:
            self._counters["prefill_chunks"] += 1
            self._counters["grouped_kernel_chunks"] += self._grouped_kernel
            self._counters["prefill_positions_held"] += min(
                start + w, true_len)
            self._counters["prefill_positions_scored"] += scored
            # NOT added to busy_s: dt is a dispatch.  The chunk's
            # compute is inside the next round's timed wait, so
            # tokens_per_sec still pays for it.
            self._chunk_times.append(dt)
            if len(self._chunk_times) > 4096:
                del self._chunk_times[:2048]
            if finished:
                self._counters["prefills"] += 1
        self._chunks_ctr.inc(engine=self._metric_name)
        if entry["trace"] is not None:
            tracing.record_span(
                "engine.prefill_chunk", entry["trace"], t0, t0 + dt,
                attrs={"engine": self._metric_name, "start": start,
                       "width": w,
                       **({"final": True} if finished else {})})

    def _finish(self, entry: dict) -> None:
        """Resolve a completed request: prompt + emitted tokens."""
        out = np.concatenate(
            [entry["tokens"],
             np.asarray(entry["emitted"], np.int32)[None]], axis=1)
        entry["out"] = {"tokens": out}
        if entry.get("export"):
            # Prefill-tier delivery: the finished pages ride the
            # response (gathered before release, while the slot still
            # holds them).
            self._attach_export(entry)
        if entry.get("park"):
            # Multi-turn session park (§5.10): publish + host-copy the
            # full context before release frees its pages.
            self._park_kv(entry)
        if entry["want_timing"]:
            now = faults.monotonic()
            entry["out"]["ttft_s"] = (
                (entry["t_first"] or now) - entry["t"])
            entry["out"]["latency_s"] = now - entry["t"]
            entry["out"]["cached_tokens"] = entry["cached"]
        if entry["trace"] is not None:
            # ONE decode span per request, stamped at delivery: first
            # token -> last token, annotated with the emitted count.
            # Per-step spans would cost the hot loop; this costs one
            # record at drain.
            end = time.perf_counter()
            tracing.record_span(
                "engine.decode", entry["trace"],
                entry["t_first_perf"] or end, end,
                attrs={"engine": self._metric_name,
                       "tokens": len(entry["emitted"])})
        entry["event"].set()

    def _drain_one(self) -> None:
        """Materialize the oldest pending emission and hand its tokens
        to their requests; retire + resolve the ones that completed.
        Counter merges are batched: one locked update per drained call,
        not per token.

        Two emission shapes ride the one stream: a prefill's [1]
        first token (counts None, col 0), and a slot-major grid with
        a per-slot ``counts`` vector — a decode round's [slots, k]
        per-step emissions ([slots, 2 k] where the model drafts: a
        step may yield two), cut at EOS/budget on device, so row s
        carries counts[s] real tokens."""
        arr, snapshot, counts, handed = self._pending.pop(0)
        if isinstance(arr, np.ndarray):
            host = arr  # the round already waited for it
        else:
            # The blocking read of a prefill's first token: the host
            # waits on the chip here, not in the drain around it, and
            # the chunk has nothing else to read.
            with self._phase("round_wait"):
                host = np.asarray(arr)
                with self._round_read(handed):
                    pass
        emitted = 0
        finished = 0
        finished_entries: List[dict] = []
        span_s = span_hit_s = 0.0
        firsts = firsts_hit = 0
        for col, entry in snapshot:
            if counts is not None:       # a round: row per slot
                toks = host[col, :int(counts[col])]
            else:                        # prefill first token: [1]
                toks = host
            for tok in toks:
                if entry["event"].is_set() or len(entry["emitted"]) >= \
                        entry["new"]:
                    break
                tok = int(tok)
                if entry["t_first"] is None:
                    entry["t_first"] = faults.monotonic()
                    now = entry["t_first_perf"] = time.perf_counter()
                    # Slot claim -> first token: the request's prefill.
                    span = now - entry["t_claim_perf"]
                    span_s += span
                    firsts += 1
                    if entry["cached"] > 0:
                        span_hit_s += span
                        firsts_hit += 1
                entry["emitted"].append(tok)
                emitted += 1
                complete = len(entry["emitted"]) >= entry["new"] or (
                    self._eos and tok == self.decode.eos_token)
                if complete:
                    # The device `done` flag froze this slot at the
                    # same step, so freeing it here never races the
                    # cache.
                    if self._slot_req[entry["slot"]] is entry:
                        # Slot table is loop-thread-owned: only _run/
                        # _drain_one rebind entries; stats() reads a
                        # GIL-atomic snapshot under the lock purely
                        # for counter consistency.
                        # kft: allow=lock-guard
                        self._slot_req[entry["slot"]] = None
                    self._finish(entry)
                    finished_entries.append(entry)
                    finished += 1
                    break
        with self._lock:
            self._counters["tokens"] += emitted
            self._counters["requests"] += finished
            self._counters["in_flight"] -= finished
            if firsts:
                self._counters["prefill_span_s_sum"] += span_s
                self._counters["first_tokens"] += firsts
                self._counters["prefill_span_hit_s_sum"] += span_hit_s
                self._counters["first_tokens_hit"] += firsts_hit
            # Delivered requests return their private KV pages to the
            # pool; published prefix pages stay resident as evictable
            # cache until LRU eviction needs them.
            for e in finished_entries:
                self._release_entry_locked(e)
            # Wake streaming readers: their tokens materialized above.
            self._emit.notify_all()
        if emitted:
            self._tok_counter.inc(emitted, engine=self._metric_name)
        if firsts:
            self._prefill_span_ctr.inc(span_s, engine=self._metric_name)

    def _record_step_timing(self, t0, end, steps, occupancy, wasted):
        """A decode round's accounting: busy time, step/occupancy
        counters, the per-token latency and inter-token-gap reservoirs,
        the step histogram and the steps-per-round reservoir — one
        discipline for what the benchmark and e2e read as percentiles.
        The round's own counters merge under the same lock (a scrape
        must never see ``fused_rounds`` ahead of ``steps``).  A round
        dispatched while the round before it was unread began, for this
        clock, where that one ended: the rounds' wall time is counted
        once (``busy_s``, the pace that clamps a width under a
        deadline)."""
        # A round may run no step: every slot it was handed had stopped
        # in the round before it, which was unread (an EOS, a drafting
        # stack's budget met early).
        norm = max(1, steps)
        if self._last_step_end is not None:
            t0 = max(t0, self._last_step_end)
        dt = end - t0
        per_tok = dt / norm
        gap = (end - self._last_step_end
               if self._last_step_end is not None else None)
        self._last_step_end = end
        # Pace EMA (loop-thread-owned): the fused-round deadline clamp
        # reads this as its step-latency estimate.
        self._step_pace_ema = per_tok if self._step_pace_ema is None \
            else ((1 - _ROUND_PACE_ALPHA) * self._step_pace_ema
                  + _ROUND_PACE_ALPHA * per_tok)
        kernel_steps = steps if self._paged_kernel else 0
        with self._lock:
            self._counters["steps"] += steps
            self._counters["decode_kernel_steps"] += kernel_steps
            if self._grouped_kernel:
                self._counters["grouped_kernel_steps"] += steps
            self._counters["occupancy_sum"] += occupancy
            self._counters["busy_s"] += dt
            self._counters["fused_rounds"] += 1
            self._counters["fused_steps_wasted"] += wasted
            self._step_times.append(per_tok)
            if len(self._step_times) > 4096:
                del self._step_times[:2048]
            if gap is not None:
                self._gap_times.append(gap / norm)
                if len(self._gap_times) > 4096:
                    del self._gap_times[:2048]
            self._round_steps.append(steps)
            if len(self._round_steps) > 4096:
                del self._round_steps[:2048]
        self._step_hist.observe(per_tok, engine=self._metric_name)
        if kernel_steps:
            self._kernel_steps_ctr.inc(kernel_steps,
                                       engine=self._metric_name)

    def _round_width(self) -> int:
        """Current fused-round step width: the adaptive value, clamped
        so ``width x pace`` stays under the tightest live deadline's
        remaining tolerance.  Deadline expiry granularity is the ROUND
        (two, where the loop runs one ahead) — the sweep only runs
        between dispatches — so an unclamped width could schedule a
        whole round past the soonest deadline and deliver nothing but a
        late 504 (docs §5.2e)."""
        width = self._round_k
        pace = self._step_pace_ema
        if width > 1 and pace and pace > 0:
            now = faults.monotonic()
            tightest = None
            for r in self._slot_req:
                if r is None or r["deadline"] is None:
                    continue
                rem = r["deadline"] - now
                tightest = rem if tightest is None \
                    else min(tightest, rem)
            if tightest is not None:
                width = min(width, max(1, int(tightest / pace)))
        return max(1, min(width, self.decode_rounds))

    def _refresh_tables_dev(self) -> None:
        """Upload the host block tables to the device (double buffer).
        Called from the overlap window right after next-round cover
        growth, so the transfer rides alongside the in-flight round's
        compute; a table mutation after that point (admission row
        reset, expiry parking) re-marks dirty and
        the next dispatch re-uploads before launching.  Under a mesh
        whose executable is not compiled yet the table placement is
        unknown: keep passing the host array — the runtime then
        transfers per dispatch."""
        import jax

        with self._lock:
            self._tables_dirty = False
            tables = self._tables.copy()
        if self.mesh is not None and self._tables_sharding is None:
            self._tables_dev = None
            return
        if self._tables_sharding is not None:
            self._tables_dev = jax.device_put(
                tables, self._tables_sharding)
        else:
            self._tables_dev = jax.device_put(tables)

    def _dispatch_round(self, live: int) -> None:
        """The first half of a decode round: a single ``decode_rounds``
        dispatch advances every live slot up to ``width`` steps with
        device-side early exit the moment all are done, and the host
        work for the NEXT round — cover growth, the double-buffered
        block-table upload — runs in the overlap window while the
        device computes.  The round joins
        ``_unread``; ``_finish_round`` reads, drains and accounts it.

        Where another decode round follows (``_another_round_follows``)
        the loop runs the next iteration and dispatches THAT round
        first, so the device finds it queued when this one ends, and the
        host's read, drain, accounting, admission and chunk dispatch run
        beside a round instead of after one.  The depth is one round.
        Nothing a round needs from the host lies in the results of the
        round before it: last tokens, lengths and ``done`` live on the
        device and are chained from call to call, ``scheduled`` already
        stands for every dispatched step, retirement is decided at
        dispatch, and a slot the device stopped (``done``) rides along
        and emits nothing.  What the next round cannot know yet it
        covers for: a drafting stack's cover counts the unread round's
        worst case too, the adaptive width sees waste and the queue one
        round late, a freed slot may be claimed while its last round is
        unread (the drain delivers by the snapshot's request objects, and
        the claim's chunk is queued behind that round on the device),
        pages come back at the drain, one round later, and admissions
        and expiries join between dispatches, so that a deadline's
        granularity becomes two rounds (``_round_width`` clamps each
        under the tightest live deadline).  Greedy tokens do not depend
        on the width nor on when a round is read: slot math is per-row
        independent, so scheduling cannot change any slot's stream."""
        from kubeflow_tpu.models.generate import decode_rounds

        kmax = self.decode_rounds
        # A drafting model's step yields one token or two, and its
        # draft layer writes one index past them.
        per = 2 if self._mtp else 1
        rnd = _Round()
        rnd.number, rnd.live = self._round_no, live
        with self._phase("round_prepare"):
            rnd.width = width = self._round_width()
            rnd.snapshot = snapshot = [
                (i, r) for i, r in enumerate(self._slot_req)
                if r is not None and not r["prefilling"]]
            # Worst-case cover for the WHOLE round before dispatch: the
            # device may write `width` new positions per slot and the
            # block tables ride in as one host-owned snapshot.  The
            # admission reservation guarantees the pages, so this never
            # blocks.  ``scheduled`` counts a drafting step as ONE token
            # until its round is read: an unread round may have written
            # as many again.
            slack = (per - 1) * sum(u.width for u in self._unread)
            # Per snapshot row: cache positions before the round.
            rnd.lengths = lengths = []
            for _, r in snapshot:
                lengths.append(r["tokens"].shape[1] + r["scheduled"])
                self._ensure_cover(
                    r, lengths[-1] + slack + per * width - 1 + self._mtp)
            if self._rounds_exec is None:
                # One executable serves EVERY adaptive width: the buffer
                # size k is static, the per-round step cap is a traced
                # operand.  Built outside the timed window (compile must
                # not pollute the step percentiles).
                self._rounds_exec = self._aot(
                    decode_rounds, self.cfg, self.params, self._state,
                    self.decode, kmax, self._tables, np.int32(kmax),
                    paged_kernel=self._paged_kernel,
                    grouped_kernel=self._grouped_kernel)
                if self.mesh is not None:
                    # The double-buffered upload must land the tables
                    # exactly where the SPMD executable expects them.
                    self._tables_sharding = \
                        self._rounds_exec.input_shardings[0][2]
            if self._tables_dirty:
                self._refresh_tables_dev()
            tables = (self._tables_dev if self._tables_dev is not None
                      else self._tables)
            # Chaos hook: sleep = slow/wedged round (deadlines expire
            # mid-round); raise = device death (_abort resolves every
            # waiter).  Outside the timed window so the injected stall
            # does not masquerade as device latency.
            faults.fire("engine.step")
        rnd.t0 = time.perf_counter()
        with self._phase("round_dispatch", width=width,
                         live=live) as phase:
            self._state, rnd.toks, rnd.counts, rnd.steps_run, \
                *rnd.drafts = self._rounds_exec(
                    self.params, self._state, tables, np.int32(width))
            rnd.handed = self._device_has_work(phase)
            # The round's counts leave the state before the next call
            # donates it, and spare arrays ride on in their place
            # (decode_rounds starts them at zero).  Their copies to the
            # host ride behind the round, so the read at the boundary
            # costs no round trip of its own.
            rnd.held = {key: self._state[key] for key in self._count_keys}
            if rnd.held:
                self._state = {**self._state, **self._count_spares.pop()}
                for count in rnd.held.values():
                    count.copy_to_host_async()
            if self._unread:
                # Loop-thread-owned key (see loop_rounds).
                # kft: allow=lock-guard
                self._counters["rounds_ahead"] += 1
            # From here _abort finds the entries this round retires.
            self._unread.append(rnd)
        # ---- overlap window: the dispatch returned as soon as the
        # round was enqueued; everything until the round before this
        # one (or this one) is read runs while the device computes.
        with self._phase("overlap"):
            # Deterministic retirement at dispatch: with no EOS a slot
            # whose remaining budget fits this round is KNOWN to finish
            # — the loop early-exits only when EVERY slot is done, so it
            # can never stop short of a still-advancing slot's budget.
            # (A drafting model's slot emits ``width`` tokens at least:
            # what it emitted is read at the boundary.)
            rnd.adds = []
            for i, r in snapshot:
                rnd.adds.append(min(r["new"] - r["scheduled"], width))
                r["scheduled"] += rnd.adds[-1]
                if not self._eos and r["scheduled"] >= r["new"]:
                    # Loop-thread-owned (see _drain_one).
                    # kft: allow=lock-guard
                    self._slot_req[i] = None
            # Double buffer: grow the NEXT round's covers and start
            # their table upload now, so the next dispatch finds the
            # transfer already done (or at least in flight) instead of
            # paying it on the critical path.
            slack += (per - 1) * width
            for i, r in snapshot:
                if self._slot_req[i] is r:
                    self._ensure_cover(
                        r, r["tokens"].shape[1] + r["scheduled"]
                        + slack + per * kmax - 1 + self._mtp)
            if self._tables_dirty:
                self._refresh_tables_dev()
            # Overlapped spill (§5.10): evacuate one cold record while
            # the round computes — the gather is enqueued behind the
            # in-flight round, so the host blocks at most where it
            # would block on the round's tokens anyway, and pool
            # pressure drains in the window PR 16 opened instead of on
            # the admission path.
            if self.host_spill_blocks:
                self._spill_tick(1)

    def _another_round_follows(self, stopping: bool) -> bool:
        """May the loop leave the round it has just dispatched unread and
        go on to dispatch the next?  It may where a slot stays live
        after that round's retirements, unless the engine is stopping
        (it drains)."""
        return not stopping and any(
            r is not None and not r["prefilling"] for r in self._slot_req)

    def _finish_round(self) -> None:
        """The second half of the oldest unread decode round: wait for
        its results, materialize them ONCE, deliver, account.  Its
        phases carry the round's own number, whichever iteration runs
        them."""
        rnd = self._unread[0]
        # Loop-thread-owned (see _device_has_work).
        # kft: allow=lock-guard
        self._round_no = rnd.number
        snapshot, lengths = rnd.snapshot, rnd.lengths
        # ``round_wait``'s own stretch is the wait for the first result;
        # the other reads and the counts made of them are ``round_read``,
        # and the facts stay on ``round_wait``, which ends where they do.
        with self._phase("round_wait") as phase:
            toks_np = np.asarray(rnd.toks)
            with self._round_read(rnd.handed):
                counts_np = np.asarray(rnd.counts)
                steps = int(rnd.steps_run)
                # The device's own counts: the steps it ran of ``width``,
                # and the cache positions those steps attended, summed
                # over slots and steps (a slot that emitted n tokens from
                # length l attended l, l + 1, ... l + n - 1): the round's
                # least cache traffic, whatever stopped a slot.
                attended = 0
                for (i, _), at in zip(snapshot, lengths):
                    n = int(counts_np[i])
                    attended += n * at + n * (n - 1) // 2
                facts = {"steps": steps, "attended": attended}
                held = {key: np.asarray(count)
                        for key, count in rnd.held.items()}
                if "mtp_counts" in held:
                    # A drafting step's two rows read a plane's pages
                    # once: ``attended`` is the device's own sum, over
                    # live slots and steps, of what the later row saw.
                    made, taken, seen = map(int, held["mtp_counts"])
                    facts.update(attended=seen, mtp_drafted=made,
                                 mtp_accepted=taken)
                    drafts_np = np.asarray(rnd.drafts[0])
                    with self._lock:
                        self._counters["mtp_drafted"] += made
                        self._counters["mtp_accepted"] += taken
                        self._counters["mtp_steps"] += steps
                    self._mtp_drafted_ctr.inc(
                        made, engine=self._metric_name)
                    self._mtp_accepted_ctr.inc(
                        taken, engine=self._metric_name)
                if self._index_planes or self.cfg.window_planes:
                    reads = self._sparse_reads((
                        (at, int(counts_np[i]))
                        for (i, _), at in zip(snapshot, lengths)), steps)
                    facts.update(reads)
                    with self._lock:
                        for key, n in reads.items():
                            self._counters[key] += n
                    for key, n in reads.items():
                        self._sparse_reads_ctr.inc(
                            n, engine=self._metric_name, kind=key)
                if "moe_touched" in held:
                    # The device's own count for this round
                    # (decode_rounds starts it at zero).
                    facts["experts_touched"] = int(held["moe_touched"])
                    with self._lock:
                        self._counters["experts_touched"] += \
                            facts["experts_touched"]
                if "moe_pairs" in held:
                    fell = dict(zip(_PAIR_KEYS,
                                    map(int, held["moe_pairs"])))
                    facts.update(fell)
                    with self._lock:
                        for key, n in fell.items():
                            self._counters[key] += n
                phase.facts(**facts)
                if rnd.held:
                    self._count_spares.append(rnd.held)
                # Freed in a phase.
                rnd.toks = rnd.counts = rnd.steps_run = rnd.drafts = None
        with self._phase("drain"):
            tok_before = self._counters["tokens"]
            # In the order the device ran them: a chunk dispatched after
            # this round (the loop was ahead) hands its first token over
            # after the round's tokens, one that came before it, before.
            bisect.insort(self._pending,
                          (toks_np, snapshot, counts_np, rnd.handed),
                          key=lambda emission: emission[3])
            while self._pending:
                self._drain_one()
            if self._mtp:
                for (i, r), add in zip(snapshot, rnd.adds):
                    # What the slot emitted is known here, not at
                    # dispatch, which counted ``add``; the covers of the
                    # rounds not yet dispatched count from it.
                    n = int(counts_np[i])
                    r["scheduled"] += n - add
                    if n and len(r["emitted"]) >= n:
                        r["mtp_drafts"].append(
                            (len(r["emitted"]) - n, drafts_np[i, :n]))
            # Drained: _abort no longer has to find its entries here.
            self._unread.pop(0)
        end = time.perf_counter()
        with self._phase("account"):
            delivered = self._counters["tokens"] - tok_before
            dispatched = steps * len(snapshot)
            wasted = max(0, dispatched - delivered)
            # Adaptive width (the PR 7 discipline on the round
            # dimension): shrink on early-exit waste or a waiting
            # admission, grow one step per full, waste-free round.
            if dispatched and (self._queue
                               or wasted > _ROUND_WASTE_FRAC * dispatched):
                self._round_k = max(1, self._round_k // 2)
            elif steps >= rnd.width and not wasted:
                self._round_k = min(self.decode_rounds, self._round_k + 1)
            self._record_step_timing(
                rnd.t0, end, steps, rnd.live * steps, wasted)
            self._fused_rounds_ctr.inc(1, engine=self._metric_name)
            if wasted:
                self._fused_wasted_ctr.inc(wasted,
                                           engine=self._metric_name)
        # kft: allow=lock-guard
        self._round_no = self._counters["loop_rounds"]

    def _run(self) -> None:
        """The loop thread.  Every statement of an iteration lies in
        one ``_phase`` (admit with wait_work inside it, housekeeping,
        prefill_dispatch, round_prepare, then the decode round's
        round_dispatch / overlap, and round_wait with round_read
        inside it / drain / account for each round the iteration reads:
        the one before where the loop runs ahead, its own where no round
        follows), so the ``loop_*_s`` sums tile the thread's wall time
        and every idle gap of the device falls into a named phase."""
        try:
            while True:
                if not self._iterate():
                    return
        except BaseException as exc:  # noqa: BLE001 — fail loudly to waiters
            self._abort(exc)

    def _iterate(self) -> bool:
        """One iteration of the loop; False once the engine is closed
        and drained."""
        # Loop-thread-owned key (one writer; stats() copies the dict),
        # bumped before the first phase so that it can name the round.
        # kft: allow=lock-guard
        self._counters["loop_rounds"] += 1
        # kft: allow=lock-guard
        self._round_no = self._counters["loop_rounds"]
        self._iter_facts.clear()
        with self._phase("admit"), self._lock:
            with self._phase("wait_work"):
                while (not self._queue
                       and all(r is None for r in self._slot_req)
                       and not self._pending and not self._stopped):
                    # Nothing queued and no live slot: the device waits
                    # for a client, not for the loop, and the open
                    # turnaround is closed without being counted.
                    self._ready_at = None
                    self._work.wait()
            if self._stopped and not self._queue \
                    and all(r is None for r in self._slot_req) \
                    and not self._pending:
                return False
            stopping = self._stopped
            past_drain = (stopping and self._drain_deadline
                          is not None and faults.monotonic()
                          > self._drain_deadline)
            expired = self._sweep_expired_locked()
            admissions = []
            if not stopping:
                free = self._free_slots_locked()
                while (free and self._queue
                       and len(self._prefilling)
                       + len(admissions) < self.admit_width):
                    pick = self._fair_pick_locked()
                    entry = self._queue[pick]
                    plan = self._plan_blocks_locked(entry)
                    if plan is None:
                        # Tokens-resident admission bound: the pool
                        # cannot reserve this request's worst case yet.
                        # It HOLDS its queue position (no starvation —
                        # the pick is stable until pages free) until
                        # retirements free pages; submit sheds new
                        # arrivals past the queue cap.
                        break
                    self._queue.pop(pick)
                    self._fair_seq += 1
                    self._fair_last[
                        entry.get("adapter_name") or ""] = \
                        self._fair_seq
                    slot = free.pop(0)
                    shared, cached = plan
                    # Claim the slot and bump in_flight in the same
                    # locked section that pops the queue: stats() must
                    # never see queue_depth==0 AND
                    # in_flight_requests==0 while a request is live
                    # (monitors treat that as "drained"), and an entry
                    # registered here is reachable by _abort even if
                    # its prefill dispatch dies.
                    entry["slot"] = slot
                    entry["cached"] = cached
                    entry["pos"] = cached
                    entry["blocks"] = list(shared)
                    entry["res_left"] = \
                        entry["res_blocks"] - len(shared)
                    # Zero-copy prefix resume: the cached blocks slide
                    # into the table's leading entries; prefill starts
                    # at the cached offset.
                    row = self._tables[slot]
                    row[:] = self.kv_pool_blocks
                    row[:len(shared)] = shared
                    self._tables_dirty = True
                    self._slot_req[slot] = entry
                    self._counters["in_flight"] += 1
                    admissions.append((entry, slot))
                self._set_queue_gauge(len(self._queue))
        with self._phase("housekeeping"):
            self._fail_expired(expired)
            if expired and self._prefilling:
                # Mid-prefill expiries leave the chunk schedule (the
                # sweep already released their pages and parked their
                # table rows); their frozen slots are safe to reclaim
                # (claim-time first-chunk freeze).
                self._prefilling = [
                    p for p in self._prefilling
                    if not any(p is e for e in expired)]
            if past_drain:
                self._abort(RuntimeError(
                    f"engine {self._metric_name!r} drain deadline "
                    "exceeded at close"))
                return False
            if stopping:
                # Refuse queued work immediately; keep stepping only
                # to drain in-flight slots.
                self._fail_queue(BatcherClosed(
                    f"engine {self._metric_name!r} is closed"))
            if self._registry is not None:
                # Hot adapter load/evict (§5.11): fold any pending
                # stack version into params between dispatches — live
                # traffic never waits, in-flight rows are never torn,
                # and no program recompiles.
                self._apply_adapter_updates()
            if self.host_spill_blocks:
                # Spill-then-admit (§5.10): evacuate LRU-cold idle
                # records to the host tier BEFORE this round's take()
                # calls (admission prefills below, the round's chunk,
                # decode covers) can destroy-evict them — pool pressure
                # degrades to a host copy, not to recompute.
                self._spill_tick()
        with self._phase("prefill_dispatch") as phase:
            chunks_before = self._counters["prefill_chunks"]
            for entry, slot in admissions:
                try:
                    self._begin_prefill(entry, slot)
                except _SpillShed as exc:
                    self._shed_admitted(entry, slot, str(exc))
            # Chunked prefill BETWEEN decode rounds: the head admission
            # (FIFO — oldest finishes first, best TTFT) gets at most ONE
            # chunk of chunk_w columns, then the loop returns to
            # decoding.  While slots are live a round saves up
            # PREFILL_ROUND_TOKENS of it, so in-flight slots stall one
            # chunk's compute every chunk_w / PREFILL_ROUND_TOKENS
            # rounds, no matter how long the arriving prompts are.
            if self._prefilling:
                self._prefill_credit += PREFILL_ROUND_TOKENS
                if self._prefill_credit >= self.chunk_w or not any(
                        r is not None and not r["prefilling"]
                        for r in self._slot_req):
                    self._prefill_credit = 0
                    entry = self._prefilling[0]
                    self._prefill_chunk(entry)
                    if not entry["prefilling"]:
                        self._prefilling.pop(0)
            phase.facts(
                admitted=len(admissions),
                chunks=self._counters["prefill_chunks"] - chunks_before)
        with self._phase("round_prepare"):
            self._set_occ_gauge(
                sum(r is not None for r in self._slot_req))
            # Kept on the engine for _abort: a drain frees a slot
            # before it resolves the slot's request.
            self._advancing = [r for r in self._slot_req
                               if r is not None and not r["prefilling"]]
            live = len(self._advancing)
        if live:
            # The round before this one, if the loop left it unread, is
            # read now that the device has this one queued behind it;
            # this one stays unread in its turn where another follows.
            self._dispatch_round(live)
            if len(self._unread) > 1:
                self._finish_round()
            if not self._another_round_follows(stopping):
                self._finish_round()
        else:
            if self._unread:  # its last live slot has just expired
                self._finish_round()
            with self._phase("drain"):
                self._last_step_end = None
                if not self._prefilling:
                    while self._pending:
                        self._drain_one()
        with self._phase("account"):
            self._set_occ_gauge(
                sum(r is not None for r in self._slot_req))
            # Pages resident (loop thread is the pool's only mutator;
            # the guarded setter only touches the locked registry on
            # change).
            self._set_kv_used_gauge(self._mgr.used_blocks())
            if self.host_spill_blocks:
                self._set_kv_spilled_gauge(
                    self._mgr.host_used_blocks())
            # Loop-thread-owned key (see loop_rounds): cumulative by
            # nature, so one clock read an iteration is enough.
            # kft: allow=lock-guard
            self._counters["loop_cpu_s"] = time.thread_time()
            self._push_loop_seconds()
        return True

    def _fail_queue(self, exc: Exception) -> None:
        with self._lock:
            queued, self._queue = self._queue, []
            self._set_queue_gauge(0)
        for entry in queued:
            self._unpin_adapter(entry)
            entry["err"] = exc
            entry["event"].set()

    def _abort(self, exc: BaseException) -> None:
        """Engine death: every waiter gets the error, nobody hangs."""
        with self._lock:
            self._stopped = True
            self._counters["in_flight"] = 0
        err = exc if isinstance(exc, Exception) else \
            RuntimeError(f"engine loop died: {exc!r}")
        self._fail_queue(err)
        # Fail live slots AND requests whose slots were already
        # deterministically retired at the dispatch of a round that
        # died or was still unread — those entries are in neither the
        # queue nor the slot table, and leaving them unresolved would
        # park their clients in submit() forever.
        for i, entry in enumerate(self._slot_req):
            if entry is not None and not entry["event"].is_set():
                self._unpin_adapter(entry)
                entry["err"] = err
                entry["event"].set()
            # Loop thread is dead or dying here; no concurrent writer
            # exists (see _drain_one).
            # kft: allow=lock-guard
            self._slot_req[i] = None
        for entry in self._advancing + [
                e for snapshot in (
                    *(rnd.snapshot for rnd in self._unread),
                    *(emission[1] for emission in self._pending))
                for _, e in snapshot]:
            if not entry["event"].is_set():
                self._unpin_adapter(entry)
                entry["err"] = err
                entry["event"].set()
        self._advancing = []
        self._unread.clear()
        self._pending.clear()
        self._prefilling.clear()
        self._set_occ_gauge(0)
