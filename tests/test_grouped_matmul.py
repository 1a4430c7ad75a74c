"""ops/grouped_matmul.py in the Pallas interpreter against
``jax.lax.ragged_dot``, the form it stands in for (models/generate.py
``_experts``), at the rehearse widths of the four configurations with
sparse experts (``benchmark/configs/*.json``: both products of an expert
layer, ``[p, e] x [held, e, 2 f]`` and ``[p, f] x [held, f, e]``).

What is compared: every row IN a group, to the tolerance of one rounding
of the output (float32: another order of summation over the
contraction); and, through the caller's select, the whole output (a row in
no group is not written by the kernel and reads zero from ``ragged_dot``).
What the chip's compiler makes of the kernel at the cells' widths:
tests/test_tpu_compile.py.
"""

import json
import pathlib

import numpy as np
import pytest

CONFIGS = pathlib.Path(__file__).parent.parent / "benchmark" / "configs"
# configuration -> the keys of its held experts and their width.
STACKS = {
    "lfm2-24b-a2b-l10": ("num_experts", "moe_intermediate_size"),
    "longcat-flash-omni-l4": ("n_routed_experts", "expert_ffn_hidden_size"),
    "dots3-note-prev-l5": ("n_routed_experts", "moe_intermediate_size"),
    "dots.vlm1.inst-l5": ("n_routed_experts", "moe_intermediate_size"),
}
ROW_TILE = 16


def _sizes(case, p, groups):
    """``sizes`` [groups] of a case over ``p`` rows."""
    rng = np.random.default_rng(len(case) + p + groups)
    if case == "empty_between_two_full":
        sizes = np.zeros(groups, np.int64)
        sizes[[0, 2]] = p // 2
    elif case == "every_group_empty":
        sizes = np.zeros(groups, np.int64)
    elif case == "tail_rows_in_no_group":
        sizes = rng.multinomial(p // 3, np.full(groups, 1 / groups))
    elif case == "one_group_holds_every_row":
        sizes = np.zeros(groups, np.int64)
        sizes[groups - 2] = p
    elif case == "boundaries_off_every_tile":
        # No boundary but the last on a multiple of the tile.
        ends = np.arange(1, groups) * p // groups
        ends += ends % ROW_TILE == 0
        assert (ends % ROW_TILE != 0).all(), ends
        sizes = np.diff(np.concatenate([[0], ends, [p]]))
    elif case in ("rows_below_one_tile", "rows_no_multiple_of_the_tile"):
        sizes = rng.multinomial(p - 2, np.full(groups, 1 / groups))
    else:
        raise AssertionError(case)
    return sizes.astype(np.int32)


# case -> (rows, row tile; None: the tile the kernel chooses, all of p)
CASES = {
    "empty_between_two_full": (64, ROW_TILE),
    "every_group_empty": (64, ROW_TILE),
    "tail_rows_in_no_group": (64, ROW_TILE),
    "one_group_holds_every_row": (64, ROW_TILE),
    "boundaries_off_every_tile": (61, ROW_TILE),
    "rows_below_one_tile": (12, None),
    "rows_no_multiple_of_the_tile": (40, ROW_TILE),
}


def _widths(stack):
    held, width = STACKS[stack]
    rehearse = json.loads((CONFIGS / f"{stack}.json").read_text())["rehearse"]
    return rehearse[held], rehearse["hidden_size"], rehearse[width]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("product", ["gate_and_up", "down"])
@pytest.mark.parametrize("stack", sorted(STACKS))
def test_the_kernel_is_ragged_dot_on_the_rows_in_a_group(stack, product,
                                                         case, dtype):
    import jax
    import jax.numpy as jnp

    from kubeflow_tpu.ops.grouped_matmul import grouped_matmul

    groups, e, f = _widths(stack)
    k, n = (e, 2 * f) if product == "gate_and_up" else (f, e)
    p, tile = CASES[case]
    sizes = _sizes(case, p, groups)
    assert sizes.sum() <= p and sizes.shape == (groups,)
    dt = jnp.dtype(dtype)
    rows = jax.random.normal(jax.random.key(p), (p, k), jnp.float32)
    weights = jax.random.normal(jax.random.key(n), (groups, k, n),
                                jnp.float32) * k ** -0.5
    rows, weights = rows.astype(dt), weights.astype(dt)
    want = jax.lax.ragged_dot(rows, weights, jnp.asarray(sizes))
    # Two column tiles where the width allows, so that a group's block
    # is revisited a tile of columns later.
    got = grouped_matmul(rows, weights, jnp.asarray(sizes), row_tile=tile,
                         column_tile=128 if n > 128 else None,
                         interpret=True)
    assert got.shape == want.shape and got.dtype == want.dtype == dt
    grouped = int(sizes.sum())
    got32 = np.asarray(got.astype(jnp.float32))
    want32 = np.asarray(want.astype(jnp.float32))
    tol = 2e-5 if dtype == "float32" else 2e-2
    if grouped:
        assert np.isfinite(got32[:grouped]).all()
        assert np.abs(got32[:grouped] - want32[:grouped]).max() < tol
    # The caller's select (``_experts``: a select, never a product).
    in_group = (np.arange(p) < grouped)[:, None]
    masked = np.asarray(jnp.where(in_group, got, 0).astype(jnp.float32))
    assert np.abs(masked - want32).max() < tol
    assert np.array_equal(masked[grouped:], np.zeros_like(masked[grouped:]))


def test_the_visits_name_every_group_and_tile_once_and_no_empty_group():
    """``_visits``: a group is visited once a row tile that holds one of
    its rows, in order; an empty group never; consecutive visits of a
    group keep its matrix (the block index the pipeline compares)."""
    import jax.numpy as jnp

    from kubeflow_tpu.ops.grouped_matmul import _visits

    sizes = np.asarray([5, 0, 40, 0, 3, 16, 0], np.int32)
    group, tile, starts, ends, visits = _visits(jnp.asarray(sizes), 64, 16)
    visits = int(visits)
    got = list(zip(np.asarray(group)[:visits].tolist(),
                   np.asarray(tile)[:visits].tolist()))
    # Rows 0-4 group 0; 5-44 group 2 (tiles 0, 1, 2); 45-47 group 4
    # (tile 2); 48-63 group 5 (tile 3).
    assert got == [(0, 0), (2, 0), (2, 1), (2, 2), (4, 2), (5, 3)]
    assert group.shape == (4 + 7 - 1,)
    assert np.asarray(starts).tolist() == [0, 5, 5, 45, 45, 48, 64]
    assert np.asarray(ends).tolist() == [5, 5, 45, 45, 48, 64, 64]
    # Past the last visit: clamped into range, never read as a visit.
    assert (np.asarray(group) < 7).all() and (np.asarray(tile) < 4).all()
    # Every group empty: one visit, of a group without a row.
    group, tile, starts, ends, visits = _visits(
        jnp.zeros((7,), jnp.int32), 64, 16)
    assert int(visits) == 1 and (int(group[0]), int(tile[0])) == (6, 0)
    assert int(starts[6]) == int(ends[6]) == 0


@pytest.mark.parametrize("p,k,n,want", [
    (512, 7168, 4096, (128, 512)),     # dots.vlm1's decode step, gate and up
    (512, 2048, 7168, (128, 1792)),    # and down
    (64, 2048, 3072, (64, 1536)),      # lfm2's decode step
    (1024, 1536, 2048, (128, 2048)),   # lfm2's chunk, down: a whole matrix
    (3072, 6144, 4096, (128, 512)),    # longcat's chunk
    (12, 256, 96, (12, 96)),           # no whole lane tile: the interpreter's
])
def test_block_sizes_follow_from_the_shapes(p, k, n, want):
    from kubeflow_tpu.ops import grouped_matmul as gm

    tm, tn = gm._tiles(p, k, n, 2)
    assert (tm, tn) == want
    assert n % tn == 0 and k * tn * 2 <= gm._WEIGHT_BLOCK_BYTES
    assert gm.supports(k, n) == (n % 128 == 0 and k % 128 == 0)
