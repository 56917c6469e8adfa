"""Model FLOPs of a training step, from the shapes of the parameter pytree.

A step needs 6 FLOPs per matmul parameter per token (2 forward, 4
backward).  Matmul parameters are the leaves that are matrices within a
layer; the embedding table is a lookup and does not count, the output
head does.  Attention layers add PaLM's term (Chowdhery et al. 2022,
appendix B): 12 * layers * heads * head_dim * sequence per token.
Recomputed work (rematerialisation) is not counted, and neither is the
mixing inside mLSTM and sLSTM cells beyond their weight matrices.
"""
from __future__ import annotations

import math

STACKED = "blocks/"
LOOKUP = "embed/table"


def total_params(shapes: dict) -> int:
    return sum(math.prod(s) for s in shapes.values())


def matmul_params(shapes: dict) -> int:
    """Parameters of the per-layer matrices and the output head.

    ``shapes`` maps leaf names to shapes; leaves under ``blocks/`` carry
    a leading layer axis, so a matrix there has three or more axes.
    """
    n = 0
    for name, shape in shapes.items():
        if name == LOOKUP:
            continue
        per_layer = len(shape) - (1 if name.startswith(STACKED) else 0)
        if per_layer >= 2:
            n += math.prod(shape)
    return n


def attention_flops_per_token(model: dict, family: str, seq: int) -> int:
    if family != "transformer":
        return 0
    head_dim = model["d_model"] // model["n_heads"]
    return 12 * model["n_layers"] * model["n_heads"] * head_dim * seq


def step_flops(shapes: dict, model: dict, family: str, batch: int, seq: int) -> int:
    per_token = 6 * matmul_params(shapes) + attention_flops_per_token(model, family, seq)
    return per_token * batch * seq
