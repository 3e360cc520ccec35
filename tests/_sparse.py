"""What the tests of the two sparse blocks share
(``test_sparse_blocks.py``, ``test_dsa_ops.py``): a block's module,
family, reference and tiny fields as one value, the way to its program's
configuration, and the mark that holds a case to one block. A third
sparse block is a row of ``MAKERS`` and of each file's ``BLOCKS``.
"""

import types

import numpy as np
import pytest

from benchmark import manifest
from ray_tpu.models import dots, glm_dsa


def _dots_cfg(fam, m):
    held = m.pop("held_experts")
    return dots.DotsConfig(**{
        **m, "held_experts": held and tuple(held),
        "layer_pattern": tuple(m["layer_pattern"])}, max_seq_len=256,
        prefill_head_groups=2)


def _glm_cfg(fam, m):
    """The family's own way to the program's configuration."""
    return fam.build(m, max_seq_len=256, remat=False).cfg


# name -> (the block's module, its family, fields -> configuration)
MAKERS = {"dots": (dots, "dots3_note", _dots_cfg),
          "glm_dsa": (glm_dsa, "glm_moe_dsa", _glm_cfg)}


def sparse_block(name: str, cut=None, **own):
    """``name``'s module (``mod``, ``slots``), family (``fam``), reference
    (``ref``), the family's tiny fields cut by ``cut`` (``m``, ``topk``),
    ``cfg(**fields)`` the program's configuration at them, and whatever
    the file states of its ``own``."""
    mod, family, make_cfg = MAKERS[name]
    fam = manifest.family(family)
    m = {**fam.TINY_FIELDS, **(cut or {})}
    return types.SimpleNamespace(
        name=name, mod=mod, slots=mod.SLOTS, fam=fam,
        ref=manifest.reference(fam), m=m, topk=m["index_topk"],
        cfg=lambda **kw: make_cfg(fam, {**m, **kw}), **own)


def only(name: str):
    """A case of the ``block`` fixture's that one block has alone."""
    return pytest.mark.parametrize("block", [name], indirect=True)


def tokens(seed: int, *shape):
    return np.random.RandomState(seed).randint(1, 256, shape).astype(np.int32)
