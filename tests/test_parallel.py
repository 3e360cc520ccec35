"""Sharding tests on the 8-device virtual CPU mesh: every parallelism layout
compiles, runs, and produces results identical to single-device execution."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel import (
    DEFAULT_RULES,
    MeshConfig,
    build_mesh,
    logical_to_mesh_spec,
    logical_tree_to_shardings,
    use_mesh,
)
from ray_tpu.train import batch_sharding, init_train_state, make_train_step


def test_logical_to_mesh_spec_dedup():
    spec = logical_to_mesh_spec(("batch", "seq", "embed"))
    # fsdp already consumed by batch -> embed falls back to replicated, and
    # the trailing None is trimmed.
    assert spec[0] == ("dp", "fsdp")
    assert spec[1] == "sp"
    assert len(spec) == 2


MESHES = [
    MeshConfig(dp=8),
    MeshConfig(fsdp=8),
    MeshConfig(fsdp=2, sp=2, tp=2),
    MeshConfig(dp=2, fsdp=2, tp=2),
    MeshConfig(fsdp=4, tp=2),
]


@pytest.mark.parametrize("mcfg", MESHES, ids=lambda m: m.describe())
def test_train_step_all_layouts(devices8, mcfg, rng):
    """One train step under each mesh layout matches the single-device result."""
    cfg = llama.LlamaConfig.tiny(n_layers=2)
    mesh = build_mesh(mcfg, devices8)
    opt = optax.adam(1e-3)

    toks = jax.random.randint(jax.random.PRNGKey(7), (8, 33), 0, cfg.vocab_size)
    # inputs/targets form: seq length 32 divides the sp axis.
    batch = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}

    # Single-device truth.
    params0 = llama.init_params(cfg, rng)
    (loss0, _), grads0 = jax.value_and_grad(llama.loss_fn, has_aux=True)(
        params0, batch, cfg
    )

    state, state_sh = init_train_state(
        lambda k: llama.init_params(cfg, k),
        llama.param_logical_axes(cfg),
        opt,
        mesh,
        key=rng,
    )
    step = make_train_step(
        lambda p, b: llama.loss_fn(p, b, cfg), opt, mesh, state_sh,
        donate_state=False,
    )
    with use_mesh(mesh):
        sharded_batch = jax.device_put(batch, batch_sharding(mesh))
        state2, metrics = step(state, sharded_batch)

    np.testing.assert_allclose(float(metrics["loss"]), float(loss0), rtol=2e-4)
    assert int(jax.device_get(state2.step)) == 1

    # Params actually sharded: under pure fsdp the wq leaf shard is 1/8 size.
    if mcfg.fsdp == 8:
        wq = state2.params["layers"]["wq"]
        shard = wq.addressable_shards[0].data
        assert shard.shape[1] == wq.shape[1] // 8


def test_opt_state_shardings_follow_param_paths(devices8, rng):
    """Adam moments for wo (shape == wq's) must use wo's transposed sharding."""
    cfg = llama.LlamaConfig.tiny(n_heads=4, n_kv_heads=4)  # hq*hd == d_model
    mesh = build_mesh(MeshConfig(fsdp=4, tp=2), devices8)
    opt = optax.adam(1e-3)
    state, state_sh = init_train_state(
        lambda k: llama.init_params(cfg, k),
        llama.param_logical_axes(cfg),
        opt,
        mesh,
        key=rng,
    )
    mu = state.opt_state[0].mu["layers"]
    # wq: (layers, embed->fsdp, heads->tp); wo: (layers, heads->tp, embed->fsdp)
    assert mu["wq"].sharding.spec == state.params["layers"]["wq"].sharding.spec
    assert mu["wo"].sharding.spec == state.params["layers"]["wo"].sharding.spec
    assert (
        state.params["layers"]["wq"].sharding.spec
        != state.params["layers"]["wo"].sharding.spec
    )


def test_param_shardings_cover_tree(devices8, rng):
    cfg = llama.LlamaConfig.tiny()
    mesh = build_mesh(MeshConfig(fsdp=4, tp=2), devices8)
    sh = logical_tree_to_shardings(llama.param_logical_axes(cfg), mesh, DEFAULT_RULES)
    params = llama.init_params(cfg, rng)
    assert jax.tree_util.tree_structure(params) == jax.tree_util.tree_structure(sh)


# ---- the layer's tp products, split (parallel/tp_products.py) ----


def _loss_and_grads(cfg, mcfg, devices, batch, seq, rng):
    """llama.loss_fn's loss and gradients unsharded and on ``mcfg``."""
    params = llama.init_params(cfg, rng)
    toks = jax.random.randint(
        jax.random.PRNGKey(7), (batch, seq + 1), 0, cfg.vocab_size)
    data = {"inputs": toks[:, :-1], "targets": toks[:, 1:]}
    fn = jax.value_and_grad(lambda p, b: llama.loss_fn(p, b, cfg)[0])
    mesh = build_mesh(mcfg, devices[:mcfg.size])
    with use_mesh(mesh):
        sharded = jax.jit(fn)(
            jax.device_put(params, logical_tree_to_shardings(
                llama.param_logical_axes(cfg), mesh)),
            jax.device_put(data, batch_sharding(mesh)))
    return fn(params, data), sharded


@pytest.mark.parametrize("mcfg,batch,seq,kw", [
    (MeshConfig(fsdp=2, tp=2), 4, 32, {}),
    # check_seq of benchmark/traffic/pretrain-4k-fsdp2tp2.json: what the
    # cell's reference check runs, one sequence a data shard
    (MeshConfig(fsdp=2, tp=2), 2, 512, {}),
    (MeshConfig(fsdp=2, tp=4), 2, 32, dict(n_heads=8, n_kv_heads=4)),
    (MeshConfig(fsdp=2, sp=2, tp=2), 2, 32, {}),
], ids=lambda v: v.describe() if isinstance(v, MeshConfig) else None)
def test_split_tp_products_give_the_unsharded_loss_and_gradients(
        devices8, rng, mcfg, batch, seq, kw):
    """The residual stream sharded by sequence over the tp group and the
    four products a layer split behind their transfers: the loss and
    every gradient are the unsharded ones (the cell's remat policy)."""
    cfg = llama.LlamaConfig.tiny(
        n_layers=2, max_seq_len=seq, remat_policy="flash_qkv", **kw)
    (loss0, grads0), (loss, grads) = _loss_and_grads(
        cfg, mcfg, devices8, batch, seq, rng)
    np.testing.assert_allclose(float(loss), float(loss0), rtol=2e-4)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5),
        grads0, grads)


def test_split_tp_products_are_in_the_sharded_step(devices8, rng):
    """The mechanism is there, not GSPMD's all-reduce of the residual
    stream: the ring's transfers in the program, and on a mesh without a
    tp group none."""
    cfg = llama.LlamaConfig.tiny(n_layers=2)
    fn = jax.jit(jax.grad(lambda p, t: llama.loss_fn(
        p, {"inputs": t, "targets": t}, cfg)[0]))
    args = (jax.eval_shape(lambda: llama.init_params(cfg, rng)),
            jax.ShapeDtypeStruct((4, 32), jnp.int32))
    texts = {}
    for mcfg in (MeshConfig(fsdp=2, tp=2), MeshConfig(fsdp=4)):
        with use_mesh(build_mesh(mcfg, devices8[:4])):
            texts[mcfg.tp] = fn.lower(*args).as_text()
    assert "collective_permute" in texts[2]
    assert "collective_permute" not in texts[1]


def test_a_sequence_the_tp_group_cannot_split_raises_by_name(devices8, rng):
    cfg = llama.LlamaConfig.tiny(n_layers=2)
    toks = jnp.zeros((4, 33), jnp.int32)
    params = jax.eval_shape(lambda: llama.init_params(cfg, rng))
    with use_mesh(build_mesh(MeshConfig(fsdp=2, tp=2), devices8[:4])):
        with pytest.raises(ValueError, match="33 rows cannot be split "
                                             "over tp=2"):
            jax.eval_shape(lambda p: llama.forward(p, toks, cfg), params)
    # a mixture of experts keeps GSPMD's layout: nothing to split
    moe = llama.LlamaConfig.tiny(n_layers=2, n_experts=4, moe_impl="dense")
    params = jax.eval_shape(lambda: llama.init_params(moe, rng))
    with use_mesh(build_mesh(MeshConfig(fsdp=2, tp=2), devices8[:4])):
        jax.eval_shape(lambda p: llama.forward(p, toks, moe), params)


@pytest.mark.parametrize("ways", [2, 4])
def test_tp_products_ring_against_the_plain_products(devices8, ways):
    """gather / scattered / in_order / pieces on a tp group of 2 and
    of 4 against x @ w on whole arrays."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel import tp_products as tpp

    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (2, 16, 8))          # rows over tp
    w_cols = jax.random.normal(ks[1], (8, 12))        # columns over tp
    a = jax.random.normal(ks[2], (2, 16, 12))         # columns over tp
    w_rows = jax.random.normal(ks[3], (12, 8))        # rows over tp

    def region(x, w_cols, a, w_rows):
        whole = tpp.in_order([x_r @ w_cols for x_r in tpp.gather(x)])
        return whole, tpp.scattered(tpp.pieces(a), w_rows), \
            tpp.in_order(tpp.pieces(a))

    with use_mesh(build_mesh(MeshConfig(dp=8 // ways, tp=ways), devices8)):
        assert tpp.ways() == ways
        got = jax.jit(tpp.over_tp(
            region, tpp.ways(),
            in_specs=(tpp.ROWS, tpp.W_COLUMNS, tpp.COLUMNS, tpp.W_ROWS),
            out_specs=(tpp.COLUMNS, tpp.ROWS, tpp.COLUMNS),
        ))(x, w_cols, a, w_rows)
    for g, want in zip(got, (x @ w_cols, a @ w_rows, a)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # no mesh: one piece, the plain products, no region
    assert tpp.ways() == 1 and tpp.over_tp(
        region, 1, in_specs=None, out_specs=None) is region
    for g, want in zip(region(x, w_cols, a, w_rows),
                       (x @ w_cols, a @ w_rows, a)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))
