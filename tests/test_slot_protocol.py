"""The protocol between the serving engine and a block
(``ray_tpu/models/slots.py``), held against every block at its ``tiny()``
configuration on the CPU: a reused slot gives a fresh engine's tokens; a
state that is no rows is refused by the three mechanisms that cut a state
at a position, by the configuration's name, and the Llama block's rows
are not; the tree a replica holds is in the serving types; the engine and
the serving tier name no block; and a block that states nothing but what
is its own is served.

What is one block's alone (its layers against its reference, its state's
bytes, its spans) is in ``tests/test_<block>_block.py``; the two sparse
blocks' in ``tests/test_sparse_blocks.py``.
"""

import ast
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import decode_engine as de
from ray_tpu.models import (dots, exaone, glm_dsa, glm_next, granite,
                            instella, lfm2, ling, llama, mimo, moe, nemotron,
                            solar)
from ray_tpu.models.decode_engine import RaggedDecoder
from ray_tpu.models.slots import Slots

# name -> (the block's module, its tiny configuration's maker)
BLOCKS = {
    "llama-dense": (llama, llama.LlamaConfig.tiny),
    "llama-experts": (llama, lambda **kw: llama.LlamaConfig.tiny(
        n_experts=4, top_k=2, moe_impl="dropless", **kw)),
    "ling": (ling, ling.LingConfig.tiny),
    "exaone": (exaone, exaone.ExaoneConfig.tiny),
    "instella": (instella, instella.InstellaConfig.tiny),
    "solar": (solar, solar.SolarConfig.tiny),
    "mimo": (mimo, mimo.MimoConfig.tiny),
    "granite": (granite, granite.GraniteConfig.tiny),
    "dots": (dots, dots.DotsConfig.tiny),
    "glm_dsa": (glm_dsa, glm_dsa.GlmDsaConfig.tiny),
    "glm_next": (glm_next, glm_next.GlmNextConfig.tiny),
    "lfm2": (lfm2, lfm2.Lfm2Config.tiny),
    "nemotron": (nemotron, nemotron.NemotronConfig.tiny),
}
# the blocks whose step counts what its indexers chose besides the
# routing: their slots state ``step_counters`` of their own
# (``dots.SparseSlots``, GLM-5.2's ``attended_rows`` behind it, and
# behind that GLM-5.3-Flash's ``index_keys_scored``)
SELECTS = ("dots", "glm_dsa", "glm_next")
ROWS = [name for name in BLOCKS if name.startswith("llama")]
OWN = [name for name in BLOCKS if name not in ROWS]


@pytest.fixture(scope="module")
def models():
    """name -> (cfg, params), each made once, when first asked for."""
    made = {}

    def get(name):
        if name not in made:
            mod, tiny = BLOCKS[name]
            cfg = tiny()
            made[name] = cfg, mod.init_params(cfg, jax.random.PRNGKey(7))
        return made[name]

    return get


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 256, n).astype(np.int32) for n in lengths]


@pytest.mark.parametrize("name", list(BLOCKS))
def test_a_reused_slot_gives_the_tokens_of_a_fresh_engine(name, models):
    """One slot, three streams one after another, the second and third
    shorter than the first: each starts from its own prompt's state,
    whatever the last stream left (a zero recurrent state, a ring partly
    zero and not stale, no row read past the stream's own length)."""
    cfg, params = models(name)
    prompts = _prompts(2, (30, 3, 17))
    kw = dict(slots=1, max_len=64, chunk_tokens=4, prompt_buckets=(8, 32))
    eng = RaggedDecoder(params, cfg, **kw)
    sids = [eng.submit(p, 9) for p in prompts]
    eng.drain()
    for sid, p in zip(sids, prompts):
        fresh = RaggedDecoder(params, cfg, **kw)
        one = fresh.submit(p, 9)
        fresh.drain()
        assert eng.finished[sid].tokens == fresh.finished[one].tokens


# ------------------------------------------------------ the refusals


def _use_prefix_cache(cfg, params, monkeypatch):
    from ray_tpu.models.kv_prefix_cache import PrefixCache

    yield "prefix cache", lambda: RaggedDecoder(
        params, cfg, slots=2, max_len=64, prefix_cache=PrefixCache(block=8))
    if not cfg.slot_model.rows_state:  # (its own prefill says so too)
        yield "prefix of cached rows", lambda: cfg.slot_model.prefill(
            params, np.ones((1, 8), np.int32), None, None, None, None, cfg,
            64, prefix=(0, 0, 0))


def _use_speculation(cfg, params, monkeypatch):
    yield "speculative decoding", lambda: RaggedDecoder(
        params, cfg, slots=2, max_len=64, spec_depth=2)
    state = cfg.slot_model.init_state(cfg, 2, 64)
    vec = jnp.zeros((2,), jnp.int32)
    yield "speculative decoding", lambda: de.decode_chunk_spec(
        params, None, state, vec, vec > 0, vec.astype(jnp.uint32),
        vec * 0.0, vec + 1.0, cfg, 2, 2, 1)


def _use_prefill_worker(cfg, params, monkeypatch):
    from ray_tpu.serve import llm_pool

    one = np.zeros((1,), np.int32)
    yield "prefill_kv", lambda: de.prefill_kv(
        params, np.ones((1, 8), np.int32), one + 8, one.astype(np.uint32),
        one * 0.0, one + 1.0, cfg, 64)
    monkeypatch.setattr(llm_pool, "build_model",
                        lambda *a, **k: (params, cfg))
    yield "PrefillWorker", lambda: llm_pool.PrefillWorker("any")


MECHANISMS = {"prefix_cache": _use_prefix_cache,
              "speculation": _use_speculation,
              "prefill_worker": _use_prefill_worker}


@pytest.mark.parametrize("mechanism", list(MECHANISMS))
@pytest.mark.parametrize("name", OWN)
def test_a_state_that_is_no_rows_is_refused_by_name(
        name, mechanism, models, monkeypatch):
    cfg, params = models(name)
    assert not cfg.slot_model.rows_state
    for word, use in MECHANISMS[mechanism](cfg, params, monkeypatch):
        with pytest.raises(ValueError,
                           match=f"{word}.*{type(cfg).__name__}"):
            use()
    if mechanism == "prefill_worker":
        eng = RaggedDecoder(params, cfg, slots=2, max_len=64,
                            prompt_buckets=(8,))
        with pytest.raises(ValueError, match="submit_prefilled"):
            eng.submit_prefilled([1, 2, 3], 4, {"k": 0, "v": 0})


@pytest.mark.parametrize("mechanism", list(MECHANISMS))
@pytest.mark.parametrize("name", ROWS)
def test_the_llama_blocks_rows_are_not_refused(
        name, mechanism, models, monkeypatch):
    cfg, params = models(name)
    assert cfg.slot_model.rows_state
    de.require_rows(cfg, "anything")
    for _, use in MECHANISMS[mechanism](cfg, params, monkeypatch):
        use()


# --------------------------------------------------- the serving types


@pytest.mark.parametrize("name", list(BLOCKS))
def test_a_replica_holds_the_serving_types(name, monkeypatch):
    """Matrices in the compute dtype, the block's ``F32_LEAVES`` float32.
    A block that unrolls its layers draws that tree itself (leaf by
    leaf, a leaf larger than a block in blocks), and it comes back from
    ``serving_params`` itself; float32 masters (the Llama block's, a
    published tree of any block) are cast once, on adoption. A block is
    4,096 numbers here: every block that draws its own tree has leaves
    above it, which come through the loop over blocks, and leaves
    under it, which are drawn whole."""
    monkeypatch.setattr(moe, "_BLOCK_ELEMS", 1 << 12)
    mod, tiny = BLOCKS[name]
    cfg = tiny(dtype="bfloat16")
    slots = de.slot_model(cfg)
    assert issubclass(slots, Slots) and slots is cfg.slot_model
    if name in OWN:
        assert slots is mod.SLOTS
    made = mod.init_params(cfg, jax.random.PRNGKey(0))
    if name in OWN:
        sizes = [a.size for a in jax.tree_util.tree_leaves(made)]
        assert min(sizes) < moe._BLOCK_ELEMS < max(sizes)
    masters = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), made)
    assert (slots.serving_params(cfg, made) is made) == (name in OWN)
    for tree in (slots.serving_params(cfg, made),
                 slots.serving_params(cfg, masters)):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            f32 = path[-1].key in slots.F32_LEAVES
            assert leaf.dtype == (jnp.float32 if f32 else jnp.bfloat16), path
    assert "final_norm" in slots.F32_LEAVES


@pytest.mark.parametrize("name", OWN)
def test_the_unrolled_blocks_share_one_copy(name):
    """The expert layer, a layer's MLP around it, the head, the loss and
    the serving cast are ``models/moe.py``'s and the protocol's: a block
    binds them and defines none of its own."""
    mod, _ = BLOCKS[name]
    assert mod.moe is moe and mod.loss_fn.__module__ == moe.__name__
    for own in ("_mlp", "_logits", "serving_params", "_F32_LEAVES"):
        assert not hasattr(mod, own), own
    assert mod.SLOTS.serving_params.__func__ \
        is Slots.serving_params.__func__
    for member in ("rows_state", "step_counters", "split", "first_token",
                   "refuse_prefix", "reports_routing"):
        if member == "step_counters" and name in SELECTS:
            assert mod.SLOTS.step_counters[:3] == Slots.step_counters
            continue
        assert member not in vars(mod.SLOTS), member


# ------------------------------------------- who knows which block


_BLOCK_NAMES = {"llama", "ling", "exaone", "instella", "solar", "mimo",
                "granite", "dots", "glm", "glmdsa", "lfm2", "nemotron"}


def _names_a_block(word: str) -> bool:
    word = word.lower()
    return any(word == b or word.startswith((b + "_", b + "config"))
               for b in _BLOCK_NAMES)


def _block_names_in(tree) -> set:
    """Every identifier, attribute and imported name of ``tree`` that
    names a block (prose may: docstrings and comments are not code)."""
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            words = [node.id]
        elif isinstance(node, ast.Attribute):
            words = [node.attr]
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            words = [part for a in node.names
                     for part in (a.name.split(".") + [a.asname or ""])]
            words += (getattr(node, "module", None) or "").split(".")
        else:
            continue
        found.update(w for w in words if _names_a_block(w))
    return found


@pytest.mark.parametrize("module, allowed", [
    # the three mechanisms that need a state of rows are the Llama
    # block's alone, and import its half
    ("models/decode_engine.py", {"llama_slots"}),
    ("serve/llm.py", set()),
    ("serve/llm_pool.py", set()),
])
def test_the_engine_and_the_serving_tier_name_no_block(module, allowed):
    import ray_tpu

    with open(f"{ray_tpu.__path__[0]}/{module}") as f:
        tree = ast.parse(f.read())
    if module == "serve/llm.py":
        # (build_model makes the one model the tier can name by size)
        tree.body = [n for n in tree.body
                     if getattr(n, "name", "") != "build_model"]
    assert _block_names_in(tree) == allowed


def test_the_ninth_block_reaches_no_private_name_of_the_eighth():
    """``models/glm_dsa.py`` runs the sparse layer that ``models/dots.py``
    owns through that module's public names alone, and its configuration
    states no field whose only reader is another module's function
    (whether a layer's latents are rescaled and its heads gated is the
    ``dots.Kind``'s to say)."""
    with open(glm_dsa.__file__) as f:
        tree = ast.parse(f.read())
    reached = {node.attr for node in ast.walk(tree)
               if isinstance(node, ast.Attribute) and node.attr[0] == "_"
               and isinstance(node.value, ast.Name) and node.value.id == "dots"}
    assert not reached, sorted(reached)
    cfg = glm_dsa.GlmDsaConfig.tiny()
    for field in ("lora_rescale", "gated_attention"):
        assert not hasattr(cfg, field), field
    assert (cfg.mla.rescale, cfg.mla.gated) == (False, False)


def test_the_tenth_block_reaches_no_private_name_of_the_blocks_it_runs():
    """``models/glm_next.py`` runs ``models/dots.py``'s sparse layer and
    ``models/solar.py``'s KDA layer through those modules' public names
    alone (the decay's form is its own ``_kda_inputs``, handed in), and
    its slot holds the three kinds the docstring names: a recurrent
    state a KDA layer, latent rows WITHOUT a rotated key, index keys
    pooled a block of rows with the open block's raw keys."""
    with open(glm_next.__file__) as f:
        tree = ast.parse(f.read())
    reached = {f"{node.value.id}.{node.attr}" for node in ast.walk(tree)
               if isinstance(node, ast.Attribute) and node.attr[0] == "_"
               and isinstance(node.value, ast.Name)
               and node.value.id in ("dots", "solar", "ling")}
    assert not reached, sorted(reached)
    cfg = glm_next.GlmNextConfig.tiny()
    assert (cfg.mla.dr, cfg.mla.rescale, cfg.mla.gated) == (0, False, False)
    assert cfg.mla.row_width == -(-cfg.kv_lora_rank // 128) * 128
    state = glm_next.SLOTS.init_state(cfg, 3, 50)
    assert state["lat"].shape == (1, 3, 50, cfg.mla.row_width)
    assert state["idx"].shape == (1, 3, 13, cfg.index_head_dim)  # ceil(50/4)
    assert state["tail"].shape == (1, 3, 3, cfg.index_head_dim)
    assert len(state["kda"]) == 4 and state["kda"][0]["s"].dtype == jnp.float32
    assert glm_next.SLOTS.row_kinds(cfg) == {
        "recurrent": (4, 0), "latent": (1, None), "index": (1, None)}
    assert set(glm_next.SLOTS.state_bytes(state)) == {
        "recurrent", "latent", "index"}
    assert glm_next.SLOTS.step_counters[3:] == (
        "selected_rows", "attended_rows", "index_keys_scored")


def test_no_block_imports_the_engine():
    import ray_tpu

    for name in ("llama", "llama_slots", "slots", "moe", "ling", "exaone",
                 "instella", "solar", "mimo", "granite", "dots",
                 "glm_dsa", "glm_next", "lfm2"):
        with open(f"{ray_tpu.__path__[0]}/models/{name}.py") as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                names = [a.name for a in node.names] + [
                    getattr(node, "module", None) or ""]
                assert not any("decode_engine" in n for n in names), name


# ------------------------------------------------------- a toy block


@dataclasses.dataclass(frozen=True)
class _ToyConfig:
    """A bag of embeddings: the state of a stream is the sum of its
    tokens' rows, the next token the argmax of that sum under the head."""
    vocab_size: int = 64
    d_model: int = 16
    n_layers: int = 1
    moe_layers: int = 0
    compute_dtype = jnp.dtype("float32")

    @property
    def slot_model(self):
        return _ToySlots


class _ToySlots(Slots):
    """States what is its own and nothing else."""

    @staticmethod
    def init_state(cfg, slots, max_len):
        return {"sum": jnp.zeros((slots, cfg.d_model), jnp.float32),
                "max_len": jnp.int32(max_len),
                "pos": jnp.zeros((slots,), jnp.int32)}

    @staticmethod
    def max_len(state):
        return state["max_len"]

    @staticmethod
    def state_bytes(state):
        return {"sum": state["sum"].size * state["sum"].dtype.itemsize}

    @staticmethod
    def step(cfg, params, prepared, tok, state, pos, active):
        assert prepared is None
        new = jnp.where(active[:, None], state["sum"] + params["embed"][tok],
                        state["sum"])
        return new @ params["lm_head"], {**state, "sum": new}

    @staticmethod
    def prefill(params, prompts, true_lens, seeds, temps, top_ps, cfg,
                slot_len, prefix=None):
        Slots.refuse_prefix(cfg, prefix)
        h = jnp.cumsum(params["embed"][prompts], axis=1)  # [F, P, D]
        toks0, logp0 = Slots.first_token(
            lambda p, rows: rows @ p["lm_head"], params, h, true_lens,
            seeds, temps, top_ps)
        last = h[jnp.arange(h.shape[0]), true_lens - 1]
        return {"sum": last}, true_lens, toks0, logp0

    @staticmethod
    def scatter(state, slots, streams, full_lens):
        return {**state, "sum": state["sum"].at[slots].set(streams["sum"]),
                "pos": state["pos"].at[slots].set(full_lens)}


def test_a_block_that_states_only_its_own_is_served():
    """``init_state``, ``max_len``, ``state_bytes``, ``step``,
    ``prefill`` and ``scatter``, and everything else the protocol's:
    the engine serves it, three streams through two slots, each the
    tokens of the model computed by hand; and refuses it what needs
    rows."""
    cfg = _ToyConfig()
    k_e, k_h = jax.random.split(jax.random.PRNGKey(3))
    params = {
        "embed": jax.random.normal(k_e, (cfg.vocab_size, cfg.d_model)),
        "lm_head": jax.random.normal(k_h, (cfg.d_model, cfg.vocab_size))}
    eng = RaggedDecoder(params, cfg, slots=2, max_len=32, chunk_tokens=4,
                        prompt_buckets=(8, 16))
    assert eng.row_kinds == {} and eng.state_bytes == {"sum": 2 * 16 * 4}
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, cfg.vocab_size, n) for n in (11, 3, 8)]
    sids = [eng.submit(p, 7) for p in prompts]
    eng.drain()
    embed, head = np.asarray(params["embed"]), np.asarray(params["lm_head"])
    for sid, p in zip(sids, prompts):
        total, want = embed[p].sum(0), []
        for _ in range(7):
            want.append(int(np.argmax(total @ head)))
            total = total + embed[want[-1]]
        assert eng.finished[sid].tokens == want
    with pytest.raises(ValueError, match="speculative decoding.*_ToyConfig"):
        RaggedDecoder(params, cfg, slots=2, max_len=32, spec_depth=2)
    with pytest.raises(ValueError, match="cached rows.*_ToyConfig"):
        _ToySlots.prefill(params, None, None, None, None, None, cfg, 32,
                          prefix=(0, 0, 0))
