"""Horizontally fused training arrays (runtime/hfta.py).

The HFTA contract at ONE fused width is BIT-identity, not allclose:
the same compiled step on the same inputs gives exactly the same
arrays — across an early-stopped peer and across a preempt/resume
boundary.  ACROSS widths (member i of a width-4 run against its
width-1 solo run) the compiler may order a reduction differently, so
the gradients agree to the last bits only, and the contract is the
same trajectory within a stated tolerance (``assert_same_trajectory``).
The solo control is a WIDTH-1 FusedTrainer run (the same vmapped
step): a plain ``Trainer`` step differs from the batched-GEMM
accumulation order at ~1e-8 as well.

Same-task FusedTrainers share one compiled step (the process-level
cache in runtime/hfta.py), so only the first run of each WIDTH pays a
trace; the width-4 reference run is still a module fixture so its 5
stepped batches are shared by the invariance, early-stop and resume
tests — the suite stays inside the tier-1 time budget.
"""

import numpy as np
import jax
import pytest

from kubeflow_tpu.models.transformer import TransformerConfig, lm_task
from kubeflow_tpu.parallel import MeshSpec
from kubeflow_tpu.runtime.checkpoint import CheckpointManager
from kubeflow_tpu.runtime.hfta import FusedTrainer, MemberSpec
from kubeflow_tpu.runtime.metrics import MetricsLogger

VOCAB, SEQ, BATCH = 64, 16, 8


def data_factory():
    r = np.random.RandomState(0)
    while True:
        yield {"tokens": r.randint(0, VOCAB, size=(BATCH, SEQ))
               .astype(np.int32)}


@pytest.fixture(scope="module")
def task(devices):
    cfg = TransformerConfig(
        vocab_size=VOCAB, d_model=16, n_layers=1, n_heads=2,
        n_kv_heads=2, d_ff=32, head_dim=8, max_seq_len=SEQ,
        dtype="float32")
    mesh = MeshSpec(data=-1).build(devices)
    init_fn, loss_fn = lm_task(cfg, mesh=mesh)
    return init_fn, loss_fn, mesh


def make(task, members, ckpt=None, every=1000):
    init_fn, loss_fn, mesh = task
    return FusedTrainer(
        init_fn=init_fn, loss_fn=loss_fn, members=members, mesh=mesh,
        checkpoint_dir=ckpt, checkpoint_every=every,
        metrics=MetricsLogger(stream=open("/dev/null", "w")))


def specs(n=4, stop=None):
    return [MemberSpec(name=f"m{i}", seed=i, lr=1e-3 * (i + 1),
                       tenant=f"t{i % 2}",
                       stop_step=(stop if i == 1 else None))
            for i in range(n)]


@pytest.fixture(scope="module")
def fused4(task):
    """The width-4 reference: specs(4) for 5 steps, no stops."""
    ft = make(task, specs(4))
    return ft, ft.fit(data_factory(), 5, log_every=10)


def member_leaves(trainer, fused_state, i):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(
        trainer.member_state(fused_state, i).params)]


def assert_bit_identical(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


# 64 float32 ulps at the parameters' scale (the norm scales are ~1.0):
# 7.6e-6, 0.4 % of the smallest lr here.
ULPS = 64 * float(np.spacing(np.float32(1.0)))


def assert_same_trajectory(a, b, lr, steps):
    """The same member at two fused widths.  ``vmap`` width may change
    a reduction's order, so a gradient differs in its last bits, and a
    step moves a parameter by lr * m / (sqrt(v) + eps): the elements
    agree within ULPS but for a handful whose gradient is of eps's own
    size (an embedding row the batch barely touches), which may land
    anywhere within Adam's reach, 2 * lr a step.  Measured over 5
    steps of 3,632 elements: members 0, 2, 3 differ by under ONE ulp
    everywhere, member 1 by more than 4 in 205, 16 in 7, 64 in 1 (by
    1 % of its lr); an lr 10 % off, or one step too many, moves more
    than 3,600 of them past 128."""
    assert len(a) == len(b)
    diff = np.concatenate([np.abs(x - y).ravel() for x, y in zip(a, b)])
    assert diff.max() <= 2 * lr * steps
    assert np.mean(diff > ULPS) <= 0.005


class TestWidthInvariance:
    def test_member_params_bit_identical_to_solo_control(self, task,
                                                         fused4):
        """Fused width-4 == width-1 per member, to the tolerance a
        reduction's order leaves: fusion must be invisible to each
        member's trajectory.  Members 0 and 3 bracket the lr/seed
        spread; 1 and 2 ride the same vmap lane mechanics."""
        ft4, s4 = fused4
        members = specs(4)
        for i in (0, 3):
            ft1 = make(task, [members[i]])
            s1 = ft1.fit(data_factory(), 5, log_every=10)
            assert int(ft1.member_state(s1, 0).step) \
                == int(ft4.member_state(s4, i).step) == 5
            assert_same_trajectory(member_leaves(ft1, s1, 0),
                                   member_leaves(ft4, s4, i),
                                   members[i].lr, 5)

    def test_member_validation(self, task):
        with pytest.raises(ValueError, match="duplicate"):
            make(task, [MemberSpec(name="a"), MemberSpec(name="a")])
        with pytest.raises(ValueError, match="at least one"):
            make(task, [])


class TestEarlyStopMasking:
    def test_stopped_member_freezes_peers_unaffected(self, task,
                                                     fused4):
        """m1 early-stops at step 2: its params freeze at the solo
        stop-step state while every peer matches the no-stop run."""
        ft = make(task, specs(4, stop=2))
        s = ft.fit(data_factory(), 5, log_every=10)
        # Everyone is inactive at the end (completing num_steps also
        # deactivates); the early stop shows in the step counters.
        assert ft.last_active == [False, False, False, False]
        steps = [int(ft.member_state(s, i).step) for i in range(4)]
        assert steps == [5, 2, 5, 5]
        # m1 == its own width-1 control run exactly stop_step steps
        # (another width: the same trajectory, not the same bits).
        ft1 = make(task, [specs(4)[1]])
        s1 = ft1.fit(data_factory(), 2, log_every=10)
        assert int(ft1.member_state(s1, 0).step) == 2
        assert_same_trajectory(member_leaves(ft1, s1, 0),
                               member_leaves(ft, s, 1),
                               specs(4)[1].lr, 2)
        # Peers == the reference run with no stop anywhere (the same
        # width, the same program: bit for bit).
        ft_full, s_full = fused4
        for i in (0, 2, 3):
            assert_bit_identical(member_leaves(ft_full, s_full, i),
                                 member_leaves(ft, s, i))


class TestResume:
    def test_resume_bit_identical_to_uninterrupted(self, task, fused4,
                                                   tmp_path):
        """Kill after 3 steps, restore_or_init every member, run to
        5: params must be bit-identical to the uninterrupted
        reference run."""
        straight, s_straight = fused4
        ckpt = str(tmp_path / "fused")
        first = make(task, specs(4), ckpt=ckpt)
        first.fit(data_factory(), 3, log_every=10)
        resumed = make(task, specs(4), ckpt=ckpt)
        s_resumed = resumed.fit(data_factory(), 5, log_every=10)
        for i in range(4):
            assert_bit_identical(
                member_leaves(straight, s_straight, i),
                member_leaves(resumed, s_resumed, i))

    def test_member_checkpoints_solo_compatible_and_metered(
            self, task, tmp_path):
        """Each member's checkpoint is an ordinary verified-manifest
        solo checkpoint (a plain CheckpointManager restores it), and
        the run exports per-member step counters + the active gauge."""
        from kubeflow_tpu.runtime.prom import (REGISTRY, parse_metrics,
                                               sample_value)
        ckpt = str(tmp_path / "fused")
        members = specs(2)
        ft = make(task, members, ckpt=ckpt)
        s = ft.fit(data_factory(), 3, log_every=10)
        for i, spec in enumerate(members):
            mgr = CheckpointManager(f"{ckpt}/{spec.name}")
            template = ft.create_member_state(spec)
            restored, start = mgr.restore_or_init(template)
            assert start == 3
            assert_bit_identical(
                [np.asarray(x) for x in
                 jax.tree_util.tree_leaves(restored.params)],
                member_leaves(ft, s, i))
        parsed = parse_metrics(REGISTRY.render())
        for name in ("m0", "m1"):
            assert sample_value(parsed, "kft_train_member_steps_total",
                                member=name) >= 3
        # Both members completed num_steps, so both deactivated.
        assert sample_value(parsed,
                            "kft_train_members_active") == 0.0
