"""SPMD training runtime: sharded state init, jitted train step, fit loop.

This is the part of the stack the reference never owned: its "training
runtime" was the external TF C++ PS fabric — workers pushing gradients to
parameter servers over gRPC every step (SURVEY.md §3.2 "HOT LOOP").  The
TPU-native inversion: one jitted SPMD step over a device mesh; gradient
averaging is a compiled psum over ICI, not network round-trips; parameter
servers do not exist.

Design choices for the hardware:
  - params live in the dtype the user chose (fp32 master weights by
    default), activations/compute in bfloat16 via the model definition —
    MXU-native;
  - ``donate_argnums`` on the state so XLA reuses HBM buffers in-place;
  - batch enters with the (data, fsdp)-sharding: single-process via an
    async ``jax.device_put``, multi-host via
    ``jax.make_array_from_process_local_data`` so each host feeds only
    its own shard (no host-side global batch);
  - all cross-device traffic is compiler-inserted from shardings; the
    train loop contains zero explicit collectives.
"""

from __future__ import annotations

import dataclasses
import logging
from collections import deque
from typing import (Any, Callable, Deque, Dict, Iterable, Optional,
                    Sequence, Tuple)

import jax
import jax.numpy as jnp
import optax
from flax import struct
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubeflow_tpu.parallel.mesh import DEFAULT_RULES, LogicalRules, batch_sharding
from kubeflow_tpu.runtime.checkpoint import CheckpointManager
from kubeflow_tpu.runtime.metrics import MetricsLogger, Timer
from kubeflow_tpu.testing import faults

log = logging.getLogger(__name__)

# (params, mutable, batch, rng) -> (loss, (metrics dict, new_mutable))
LossFn = Callable[
    [Any, Any, Any, jax.Array],
    Tuple[jax.Array, Tuple[Dict[str, jax.Array], Any]],
]


class TrainState(struct.PyTreeNode):
    """Minimal sharded train state: a pytree jit moves as one argument.

    ``mutable`` holds non-differentiated model collections (batch_stats for
    BatchNorm models, cache, etc.); pure models leave it as an empty dict.
    """

    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array
    mutable: Any = struct.field(default_factory=dict)


def param_shardings(
    abstract_params: Any, mesh: Mesh, rules: LogicalRules = DEFAULT_RULES
) -> Any:
    """Derive NamedShardings for a (possibly logically-annotated) param tree.

    Params created under ``nn.with_logical_partitioning`` carry logical axis
    metadata; everything else is replicated.  This single function is what
    makes "change the parallelism = change the rule table" true for every
    model in models/.
    """
    specs = nn.get_partition_spec(abstract_params)
    mesh_specs = nn.logical_to_mesh(specs, list(rules))
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec)
        if isinstance(spec, PartitionSpec)
        else NamedSharding(mesh, PartitionSpec()),
        mesh_specs,
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def _opt_shardings(
    abstract_opt: Any, abstract_params: Any, p_shardings: Any, replicated: Any
) -> Any:
    """Derive optimizer-state shardings *structurally* from the param tree.

    Optax states embed param-structured subtrees (adam's mu/nu, momentum's
    trace, ...), so every param-derived optimizer leaf's key path *ends
    with* the key path of its param.  Matching on path suffix (longest
    first) gives each leaf the sharding of exactly its own param — two
    params with identical shape/dtype but different shardings can no longer
    collide the way a (shape, dtype)-keyed lookup lets them.  Non-param
    leaves (step counters, schedules) fall back to replicated.
    """
    p_flat, _ = jax.tree_util.tree_flatten_with_path(abstract_params)
    s_flat, _ = jax.tree_util.tree_flatten_with_path(
        p_shardings, is_leaf=lambda x: isinstance(x, NamedSharding)
    )
    by_path = {
        tuple(path): (leaf.shape, sh)
        for (path, leaf), (_, sh) in zip(p_flat, s_flat)
    }

    def assign(path, leaf):
        path = tuple(path)
        for i in range(len(path)):  # longest suffix first
            hit = by_path.get(path[i:])
            if hit is not None:
                shape, sh = hit
                return sh if getattr(leaf, "shape", None) == shape else replicated
        return replicated

    return jax.tree_util.tree_map_with_path(assign, abstract_opt)


@dataclasses.dataclass
class Trainer:
    """Generic SPMD trainer over a mesh.

    init_fn: rng -> (params, mutable); params may carry ``nn.Partitioned``
      logical-axis boxes (models/ helpers produce exactly this shape).
    loss_fn: (params, mutable, batch, rng) ->
      (scalar loss, (metrics dict, new_mutable))
    """

    init_fn: Callable[[jax.Array], Any]
    loss_fn: LossFn
    tx: optax.GradientTransformation
    mesh: Mesh
    rules: LogicalRules = DEFAULT_RULES
    checkpoints: Optional[CheckpointManager] = None
    checkpoint_every: int = 1000
    metrics: MetricsLogger = dataclasses.field(default_factory=MetricsLogger)
    # Useful-FLOPs per example and the chip's peak, for MFU reporting
    # (either missing = no MFU; runtime.metrics.peak_flops is None off-TPU).
    flops_per_example: float = 0.0
    peak_flops_per_chip: Optional[float] = None

    def __post_init__(self) -> None:
        self._train_step = None
        self._multi_steps: Dict[int, Callable] = {}
        self._stackers: Dict[Any, Callable] = {}
        self._last_metrics: Dict[str, float] = {}

    @property
    def last_metrics(self) -> Dict[str, float]:
        """Scalar metrics from the final step of the last fit() call
        (empty before any fit) — the public read for callers that want
        the end-of-run loss/throughput without streaming the logger."""
        return dict(self._last_metrics)

    # -- state ------------------------------------------------------------

    def create_state(self, seed: int = 0) -> TrainState:
        """Initialize params *already sharded*: jit with out_shardings means
        each device materializes only its shard — a model larger than one
        chip's HBM initializes fine."""
        rng = jax.random.key(seed)

        def init(rng):
            init_rng, state_rng = jax.random.split(rng)
            params, mutable = self.init_fn(init_rng)
            params = nn.unbox(params)  # strip logical-metadata boxes
            opt_state = self.tx.init(params)
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=opt_state,
                rng=state_rng,
                mutable=nn.unbox(mutable),
            )

        abstract = jax.eval_shape(init, rng)
        # Re-run the boxed init abstractly to recover logical axis metadata
        # for the params subtree.
        abstract_boxed, _ = jax.eval_shape(lambda r: self.init_fn(r), rng)
        p_shardings = param_shardings(abstract_boxed, self.mesh, self.rules)
        replicated = NamedSharding(self.mesh, PartitionSpec())

        state_shardings = TrainState(
            step=replicated,
            params=p_shardings,
            opt_state=_opt_shardings(
                abstract.opt_state,
                nn.unbox(abstract_boxed),
                p_shardings,
                replicated,
            ),
            rng=replicated,
            mutable=jax.tree_util.tree_map(lambda _: replicated, abstract.mutable),
        )
        self._state_shardings = state_shardings
        init_jit = jax.jit(init, out_shardings=state_shardings)
        state = init_jit(rng)
        # Where the params actually landed — a mesh that did not take
        # effect shows here as every leaf on one device.
        spans = [len(leaf.sharding.device_set)
                 for leaf in jax.tree_util.tree_leaves(state.params)]
        log.info("train state placed: %d param leaves, each on %d..%d of "
                 "the mesh's %d device(s)", len(spans), min(spans),
                 max(spans), self.mesh.devices.size)
        return state

    # -- step -------------------------------------------------------------

    def _step_body(self, state: TrainState, batch: Any):
        rng, step_rng = jax.random.split(state.rng)

        def loss(params):
            # Mesh + rule contexts make the models' logical sharding
            # constraints (nn.with_logical_constraint) bind at trace
            # time; without them constraints are silent no-ops.
            with self.mesh, nn.logical_axis_rules(list(self.rules)):
                return self.loss_fn(params, state.mutable, batch, step_rng)

        (loss_val, (aux, new_mutable)), grads = jax.value_and_grad(
            loss, has_aux=True
        )(state.params)
        updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt,
            rng=rng,
            mutable=new_mutable,
        )
        metrics = {
            "loss": loss_val,
            "grad_norm": optax.global_norm(grads),
            **aux,
        }
        return new_state, metrics

    def compile_step(self) -> Callable[[TrainState, Any], Tuple[TrainState, Dict]]:
        if self._train_step is not None:
            return self._train_step
        self._train_step = jax.jit(self._step_body, donate_argnums=(0,))
        return self._train_step

    def compile_multi_step(
        self, k: int
    ) -> Callable[[TrainState, Any], Tuple[TrainState, Dict]]:
        """K train steps fused into one device program (host-loop fusion).

        ``lax.scan`` over batches stacked on a leading [k, ...] axis: one
        dispatch, one readiness check, and one metrics read amortize over
        k steps.  For short step times, or a busy host, per-step host
        overhead is what separates the measured step from the device
        step; fusing divides it by k.  Returned metrics are the last step's (losses of
        the k steps differ only by one step of optimizer progress).
        """
        if k in self._multi_steps:
            return self._multi_steps[k]

        def multi(state: TrainState, batches: Any):
            def body(st, b):
                return self._step_body(st, b)

            return jax.lax.scan(body, state, batches)

        def multi_repeat(state: TrainState, batch: Any):
            # Same k-step program but over ONE batch used k times (no
            # stacked xs, no per-iteration slice materialization) — the
            # steady-state benchmarking shape, where every chunk batch
            # is the same staged buffer.
            def body(st, _):
                return self._step_body(st, batch)

            return jax.lax.scan(body, state, None, length=k)

        multi_jit = jax.jit(multi, donate_argnums=(0,))
        repeat_jit = jax.jit(multi_repeat, donate_argnums=(0,))

        def run(state: TrainState, batches: Any,
                _leaves=jax.tree_util.tree_leaves):
            if isinstance(batches, (list, tuple)):
                # Repeated-batch detection is by LEAF identity: a
                # re-sharded staged batch comes back as a fresh dict
                # around the identical device buffers (device_put
                # short-circuits per leaf, tree_map rebuilds the
                # container), so container identity would never match.
                first = _leaves(batches[0])
                if all(
                    len(ls) == len(first)
                    and all(a is b for a, b in zip(ls, first))
                    for ls in (_leaves(x) for x in batches[1:])
                ):
                    state, metrics = repeat_jit(state, batches[0])
                else:
                    state, metrics = multi_jit(
                        state, self.stack_batches(list(batches)))
            else:  # already stacked [k, ...]
                state, metrics = multi_jit(state, batches)
            return state, jax.tree_util.tree_map(lambda a: a[-1], metrics)

        self._multi_steps[k] = run
        return run

    def stack_batches(self, batches: Sequence[Any]) -> Any:
        """Stack k sharded batches on a new leading steps axis [k, ...]
        for compile_multi_step's scan.  Device-side stack (one small
        program; scan slices restore the per-batch layout), explicit
        out-shardings so the batch dim stays sharded over the dp axes
        on axis 1."""
        def spec(x):
            # One source of truth for the batch-over-dp convention:
            # mesh.batch_sharding, with a leading None for the new
            # steps axis.  0-d leaves stack to rank 1, unsharded.
            ndim = getattr(x, "ndim", 0)
            if ndim == 0:
                return NamedSharding(self.mesh, PartitionSpec(None))
            inner = batch_sharding(self.mesh, ndim=ndim).spec
            return NamedSharding(self.mesh, PartitionSpec(None, *inner))

        # The jit wrapper must be cached: a fresh jax.jit per call is a
        # fresh trace cache, i.e. a recompile of the (trivial) stack
        # program on every chunk.
        key = (len(batches),
               jax.tree_util.tree_structure(batches[0]),
               tuple((getattr(x, "shape", None), str(getattr(x, "dtype",
                     None)))
                     for x in jax.tree_util.tree_leaves(batches[0])))
        stacker = self._stackers.get(key)
        if stacker is None:
            out_shardings = jax.tree_util.tree_map(spec, batches[0])
            stacker = jax.jit(
                lambda *xs: jax.tree_util.tree_map(
                    lambda *ys: jnp.stack(ys), *xs),
                out_shardings=out_shardings,
            )
            self._stackers[key] = stacker
        return stacker(*batches)

    def shard_batch(self, batch: Any) -> Any:
        """Place a host batch onto the mesh, batch-dim sharded over dp axes.

        Single-process: an async ``device_put`` of the whole batch.
        Multi-host: the caller passes only this process's shard
        (global_batch / process_count rows) and
        ``make_array_from_process_local_data`` assembles the global array —
        no host ever materializes or transfers the full global batch.
        """
        multihost = jax.process_count() > 1

        def put(x):
            sharding = batch_sharding(self.mesh, ndim=getattr(x, "ndim", 1))
            if multihost:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)

        return jax.tree_util.tree_map(put, batch)

    # -- loop -------------------------------------------------------------

    def fit(
        self,
        data: Iterable[Any],
        num_steps: int,
        *,
        state: Optional[TrainState] = None,
        examples_per_step: int = 0,
        log_every: int = 10,
        steps_per_call: int = 1,
        on_step: Optional[Callable[[int], None]] = None,
    ) -> TrainState:
        """Run the train loop with metrics + periodic async checkpoints.

        Resumes from the latest checkpoint automatically when a manager is
        attached — the whole preemption-recovery contract is "rerun the
        same command", replacing the reference's sleep-forever restart hack
        (tf-controller-examples/tf-cnn/launcher.py:86-90).

        Dispatch discipline (this loop IS the fast loop — no bespoke bench
        loop needed):
          - steps are dispatched asynchronously; the host never blocks on
            the device except at log/checkpoint boundaries, so XLA keeps
            the chip busy back-to-back;
          - the *next* batch is sharded onto the device while the current
            step is still executing (host->HBM transfer overlaps compute);
          - step time is averaged over the window since the last sync —
            a per-step host sync would measure host<->device round-trip
            latency, not device throughput;
          - dispatch depth is bounded at 2 CALLS: the host blocks on the
            result from two calls ago, so at most two calls' input
            buffers are ever in flight no matter how `log_every` is set
            (an unbounded loop would queue every batch's HBM buffer
            ahead of the device).  With steps_per_call=1 that is two
            batches; with steps_per_call=k it is up to two stacked
            [k, ...] chunks (~2k batches of HBM) — size k to headroom;
          - ``steps_per_call=k`` fuses k steps into one device program
            (compile_multi_step's lax.scan): one dispatch, one readiness
            check, and one possible metrics read per k steps.  Use when
            per-step host overhead is visible next to the device step —
            short steps, busy hosts, or high-latency dispatch paths.
            Logging and checkpoints land on call boundaries.

        Supervision hooks: each loop iteration fires the
        ``train.step`` fault site BEFORE the dispatch (a scripted
        ``raise`` models a step fault the supervisor must recover
        from), and ``on_step(i_next)`` runs at each call boundary —
        runtime/supervisor.py stamps its heartbeat and stall watchdog
        there.
        """
        if state is None:
            state = self.create_state()
        start_step = 0
        if self.checkpoints is not None:
            state, start_step = self.checkpoints.restore_or_init(state)
        if start_step >= num_steps:
            self._last_metrics = {}
            return state
        step_fn = self.compile_step()
        n_chips = self.mesh.devices.size

        it = iter(data)
        if start_step:
            # Don't replay already-trained batches after a resume: fast-path
            # datasets that can seek, drain otherwise.
            seek = getattr(data, "seek", None)
            if callable(seek):
                seek(start_step)
            else:
                for _ in range(start_step):
                    next(it)
        final_metrics: Dict[str, Any] = {}
        k = max(1, int(steps_per_call))
        multi_fn = self.compile_multi_step(k) if k > 1 else None
        batch = self.shard_batch(next(it))
        timer = Timer()
        timer.start()
        window_steps = 0
        inflight: Deque[Any] = deque()
        i = start_step
        while i < num_steps:
            faults.fire("train.step")
            if multi_fn is not None and i + k <= num_steps:
                chunk = [batch]
                for _ in range(k - 1):
                    chunk.append(self.shard_batch(next(it)))
                state, metrics = multi_fn(state, chunk)
                advance = k
            else:
                state, metrics = step_fn(state, batch)
                advance = 1
            i_next = i + advance
            window_steps += advance
            if i_next < num_steps:
                # Overlaps with the async step above.
                batch = self.shard_batch(next(it))
            inflight.append(metrics["loss"])
            if len(inflight) > 2:
                # Backpressure: in steady state this result is already
                # done, so the wait is free — it only paces the host.
                jax.block_until_ready(inflight.popleft())
            if on_step is not None:
                on_step(i_next)
            last = i_next - 1
            if log_every and (i_next // log_every > i // log_every
                              or i_next == num_steps):
                loss = float(metrics["loss"])  # device sync
                dt = timer.stop() / window_steps
                timer.start()
                window_steps = 0
                self.metrics.step(
                    step=last,
                    step_time_s=dt,
                    examples_per_step=examples_per_step,
                    flops_per_step=self.flops_per_example * examples_per_step * 3
                    if self.flops_per_example else None,
                    n_chips=n_chips,
                    peak_flops_per_chip=self.peak_flops_per_chip,
                    loss=loss,
                )
            if (
                self.checkpoints is not None
                and i_next // self.checkpoint_every > i // self.checkpoint_every
            ):
                self.checkpoints.save(last, state)
            final_metrics = metrics
            i = i_next
        if self.checkpoints is not None:
            self.checkpoints.save(num_steps - 1, state, force=True)
            self.checkpoints.wait()
        self._last_metrics = {
            k: float(v) for k, v in final_metrics.items()
            if jnp.ndim(v) == 0
        }
        return state
