"""``ops/grouped_matmul.py``: the Pallas kernel, run in interpret mode,
against ``jax.lax.ragged_dot`` (what the same function is off the TPU):
forward, both gradients, empty groups, rows that do not fill a tile,
and the stacked form a serving program's layer scan uses. That the
kernel compiles for the chip at OLMoE's shapes is
``tests/test_tpu_compile_llama.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.grouped_matmul import (group_metadata, grouped_matmul,
                                        tiling)

CASES = {
    # name: (rows, k, n, tm, tn, group sizes or number of groups)
    "even": (64, 128, 256, 16, 128, 8),
    "empty-groups": (64, 128, 256, 64, 128, [0, 10, 0, 0, 30, 1, 0, 23]),
    "ragged-rows": (100, 128, 256, 32, 128, 8),
    "one-group": (256, 256, 128, 64, 128, [256, 0, 0, 0]),
    "decode-like": (24, 128, 128, None, None, 16),
}


def _case(name):
    m, k, n, tm, tn, groups = CASES[name]
    keys = jax.random.split(jax.random.PRNGKey(len(name)), 4)
    if isinstance(groups, int):
        ids = jax.random.randint(keys[0], (m,), 0, groups)
        sizes = jnp.bincount(ids, length=groups).astype(jnp.int32)
    else:
        sizes = jnp.asarray(groups, jnp.int32)
    lhs = jax.random.normal(keys[1], (m, k), jnp.float32)
    rhs = jax.random.normal(keys[2], (sizes.shape[0], k, n), jnp.float32)
    weight = jax.random.normal(keys[3], (m, n), jnp.float32)
    return lhs, rhs, sizes, weight, dict(tm=tm, tn=tn)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_matches_ragged_dot(name):
    lhs, rhs, sizes, weight, tile = _case(name)

    def loss(fn):
        return lambda a, b: (fn(a, b) * weight).sum()

    kernel = lambda a, b: grouped_matmul(  # noqa: E731
        a, b, sizes, interpret=True, **tile)
    plain = lambda a, b: grouped_matmul(a, b, sizes)  # noqa: E731
    np.testing.assert_allclose(np.asarray(kernel(lhs, rhs)),
                               np.asarray(plain(lhs, rhs)), atol=2e-4)
    got = jax.grad(loss(kernel), (0, 1))(lhs, rhs)
    want = jax.grad(loss(plain), (0, 1))(lhs, rhs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4)


def test_a_stack_is_read_in_place_at_the_layer():
    lhs, rhs, sizes, _, tile = _case("empty-groups")
    stack = jnp.stack([rhs * 0, rhs, rhs * 2])
    for use in (dict(interpret=True, **tile), dict()):
        got = jax.jit(lambda layer: grouped_matmul(
            lhs, stack, sizes, layer=layer, **use))(jnp.int32(1))
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(grouped_matmul(lhs, rhs, sizes)),
            atol=2e-4)


def test_the_grid_visits_no_empty_group():
    """The kernel's steps are the (row tile, group) pairs that hold a
    row: an expert nobody was routed to is never read."""
    sizes = jnp.asarray([0, 10, 0, 0, 30, 1, 0, 23], jnp.int32)
    (offsets, group_ids, tiles), steps = group_metadata(
        sizes, 64, 16, visit_empty=False)
    steps = int(steps)
    visited = np.asarray(group_ids)[:steps].tolist()
    assert set(visited) == {1, 4, 5, 7}
    # 10 rows: tile 0; 30: tiles 0-2; 1: tile 2; 23: tiles 2-3
    assert visited == [1, 4, 4, 4, 5, 7, 7]
    assert np.asarray(tiles)[:steps].tolist() == [0, 0, 1, 2, 2, 2, 3]
    assert np.asarray(offsets).tolist() == [0, 0, 10, 10, 10, 40, 41, 41, 64]
    _, steps_all = group_metadata(sizes, 64, 16, visit_empty=True)
    assert int(steps_all) == steps + 4  # tgmm zeroes the four empty ones


def test_tiling_fits_the_shapes():
    assert tiling(64, 2048, 1024) == (64, 1024)    # a decode step: one tile
    assert tiling(8192, 1024, 2048) == (256, 2048)  # a 1024-token prefill
    assert tiling(40, 2048, 1024) == (48, 1024)    # rows padded to 16
    assert tiling(8192, 2048, 3072) == (256, 1024)  # tn divides n
    assert tiling(64, 128, 96) == (64, 96)         # under a lane tile
    # an expert's [k, tn] block stays within 8 MiB
    assert tiling(8192, 14336, 4096) == (256, 256)
    assert tiling(8192, 4096, 14336, itemsize=4) == (256, 512)


def test_a_width_that_is_not_whole_lane_tiles_is_ragged_dot():
    """Nemotron-3-Nano's experts at a tiny analogue (384 -> 232 -> 384
    for 2,688 -> 1,856 -> 2,688: 232 = 14.5 x 16 as 1,856 = 14.5 x 128),
    the kernel in the interpreter against ``ragged_dot``: the up product
    with its matrix stored ``[E, N, K]`` (``transpose_rhs``: 232 is then
    no matrix's minor dimension; one block holds all of N), the down
    product with a CONTRACTED width of 232, rows that belong to no group
    behind the held ones left alone, empty groups never visited."""
    keys = jax.random.split(jax.random.PRNGKey(70), 4)
    d, f, rows = 384, 232, 48
    sizes = jnp.asarray([0, 7, 0, 12, 1, 0, 9, 3], jnp.int32)  # 32 of 48
    held = int(sizes.sum())
    x = jax.random.normal(keys[0], (rows, d), jnp.float32)
    up_t = jax.random.normal(keys[1], (8, f, d), jnp.float32)
    down = jax.random.normal(keys[2], (8, f, d), jnp.float32)
    assert tiling(rows, d, f) == (48, f)  # (no whole-lane divisor: all)
    want = jax.lax.ragged_dot(x, jnp.swapaxes(up_t, 1, 2), sizes)
    for use in (dict(interpret=True), dict()):
        got = grouped_matmul(x, up_t, sizes, transpose_rhs=True, **use)
        assert got.shape == (rows, f)
        np.testing.assert_allclose(got[:held], want[:held], atol=2e-4)
    mid = jax.random.normal(keys[3], (rows, f), jnp.float32)
    want = jax.lax.ragged_dot(mid, down, sizes)
    for tn in (128, 384):
        got = grouped_matmul(mid, down, sizes, interpret=True, tn=tn)
        np.testing.assert_allclose(got[:held], want[:held], atol=2e-4)
    # a stack read in place at the layer, transposed
    stack = jnp.stack([up_t * 0, up_t])
    got = jax.jit(lambda layer: grouped_matmul(
        x, stack, sizes, layer=layer, transpose_rhs=True, interpret=True))(
            jnp.int32(1))
    np.testing.assert_allclose(
        got[:held], jax.lax.ragged_dot(x, jnp.swapaxes(up_t, 1, 2),
                                       sizes)[:held], atol=2e-4)


def _older_expert_widths():
    """(configuration, hidden size, an expert's width) of every
    configuration file of the benchmark that has experts but this PR's."""
    import os

    from benchmark import manifest

    out = []
    for name in sorted(os.listdir(os.path.join(manifest.HERE, "configs"))):
        name = name[:-len(".json")]
        if name.startswith("nemotron"):
            continue
        _, m = manifest.model(name)
        if m.get("n_experts"):
            out.append((name, m["d_model"], m["d_ff"]))
    return out


# (k, n) -> tn at a decode step's rows and a prefill's, as the parent's
# ``tiling`` gave them (PR 69, ``git show 0d27b89:ray_tpu/ops/
# grouped_matmul.py``): the tiles the older cells were measured at
PINNED = {
    (5120, 1536): 768, (1536, 5120): 1024,     # dots3
    (6144, 2048): 512, (2048, 6144): 2048,     # GLM-5.2, K-EXAONE
    (4096, 2048): 1024, (2048, 4096): 2048,    # GLM-5.3-Flash, MiMo-V2.5
    (4096, 768): 768, (768, 4096): 2048,       # Granite
    (2048, 1408): 1408, (1408, 2048): 2048,    # Instella-MoE
    (2048, 1792): 1792, (1792, 2048): 2048,    # LFM2
    (2560, 768): 768, (768, 2560): 512,        # Ling
    (2048, 1024): 1024, (1024, 2048): 2048,    # OLMoE
    (4096, 1280): 256, (1280, 4096): 2048,     # Solar-Open2
}


def test_every_older_width_keeps_the_tile_it_had():
    """``tiling``'s new clause (halving that runs out of twos on ONE
    lane tile takes the largest whole-lane divisor within the budget:
    2,688 = 21 x 128 gets 896, not 128) moves no pair an older
    configuration sends: every (hidden size, expert width) of the
    configuration files, both products, at a decode step's rows and a
    prompt's, gets the tile the parent gave it. A halving that stops on
    two lane tiles or more stands (dots3's 5,120 under 2,048: 1,024,
    though 1,280 divides it), which is why the clause is no wider."""
    found = _older_expert_widths()
    assert len(found) == 11 and len({(d, f) for _, d, f in found}) == 9
    for name, d, f in found:
        for k, n in ((d, f), (f, d)):
            for rows in (16, 192, 1536, 8192):
                tm, tn = tiling(rows, k, n)
                assert tn == PINNED[(k, n)], (name, k, n, rows, tn)
                assert tm == min(256, -(-rows // 16) * 16)
    # this PR's widths: the down product's 21 lane tiles in three blocks
    # of seven, the up product's 14.5 lane tiles in one block (stored
    # [F, D]: ``transpose_rhs``)
    assert tiling(192, 1856, 2688) == (192, 896)
    assert tiling(192, 2688, 1856) == (192, 1856)
    assert tiling(1536, 1856, 2688) == (256, 896)
    # an explicit tile stands
    assert tiling(192, 1856, 2688, tn=128) == (192, 128)
    # a transposed block that would not fit the kernel's VMEM twice over
    # is refused when the program is traced
    with pytest.raises(ValueError, match="whole lane tiles"):
        jax.eval_shape(lambda a, b: grouped_matmul(
            a, b, jnp.zeros((2,), jnp.int32), transpose_rhs=True,
            interpret=True), jax.ShapeDtypeStruct((64, 16384), jnp.bfloat16),
            jax.ShapeDtypeStruct((2, 1000, 16384), jnp.bfloat16))


@pytest.mark.parametrize("shape, want", [
    # K-EXAONE's experts, 6144 -> 2048: 640 columns fit the 8 MiB and
    # halving 640 never divides 2048; the largest whole-lane divisor
    # within the budget, not all 2,048 columns (a 25 MB block)
    ((512, 6144, 2048), (256, 512)), ((8192, 6144, 2048), (256, 512)),
    ((512, 2048, 6144), (256, 2048)),
    # Ling's and OLMoE's, as they were
    ((256, 2560, 768), (256, 768)), ((256, 768, 2560), (256, 512)),
    ((64, 2048, 1024), (64, 1024)),
    # no whole-lane divisor at all: every column
    ((64, 6144, 200), (64, 200))])
def test_tiling_keeps_whole_lanes_where_halving_leaves_them(shape, want):
    tm, tn = tiling(*shape)
    assert (tm, tn) == want
    assert shape[2] % tn == 0 and (tn % 128 == 0 or tn == shape[2])
    if tn % 128 == 0:
        assert shape[1] * tn * 2 <= 8 * 1024 * 1024
