"""Operations and bytes a dense GQA decoder needs, from its sizes alone.

``dims`` is ``spec.dims(config)``.  Counts are of the multiply-adds the
model needs (2 operations each): the projections and MLP of every layer,
attention over the positions a token really attends (its own included),
and the vocabulary projection only where a token is sampled.  Norms,
rotary embeddings, biases and softmax are left out: they are under 1% of
the operations at these widths.  Bytes are of bfloat16 (2 bytes).
"""
from __future__ import annotations

BYTES = 2  # bfloat16


def layer_params(dims: dict) -> int:
    """Weights of one layer's matrix multiplications."""
    d, h, kv, hd, ff = (dims["d_model"], dims["heads"], dims["kv_heads"],
                        dims["head_dim"], dims["d_ff"])
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff


def dense_flops_per_token(dims: dict) -> int:
    """Projections and MLP of every layer, for one token."""
    return 2 * dims["layers"] * layer_params(dims)


def attention_flops(dims: dict, attended: int) -> int:
    """Scores and weighted values over ``attended`` positions, summed over
    every layer, for one token (q.k and p.v, 2 operations per
    multiply-add each)."""
    return 4 * dims["layers"] * attended * dims["heads"] * dims["head_dim"]


def logits_flops(dims: dict) -> int:
    return 2 * dims["d_model"] * dims["vocab"]


def prefill_attended(start: int, n: int) -> int:
    """Positions attended by the ``n`` tokens at ``start .. start+n-1``:
    each attends every earlier position and itself."""
    return n * start + n * (n + 1) // 2


def kv_bytes_per_position(dims: dict) -> int:
    """K and V of one position, one layer."""
    return 2 * dims["kv_heads"] * dims["head_dim"] * BYTES


def decode_attention_bytes(dims: dict, attended: int) -> int:
    """Least bytes the decode attention kernel moves for one sequence in
    one layer: K and V of every attended position, the query in and the
    output out."""
    qo = 2 * dims["heads"] * dims["head_dim"] * BYTES
    return attended * kv_bytes_per_position(dims) + qo
