"""Operations and bytes a looped decoder's serving step NEEDS, from its
shapes alone (as lib/counts.py has them for the ALS kernels): the model
FLOPs of the step's REAL tokens, so padding, recomputation and a
kernel's extra passes count as time and never as work."""

from __future__ import annotations


def layer_params(model: dict) -> int:
    """Matrix parameters of one decoder layer: four attention
    projections and the three of the gated MLP."""
    d, f = model["hidden_size"], model["intermediate_size"]
    a = model["num_attention_heads"] * model["head_dim"]
    return 4 * d * a + 3 * d * f


def looped_lm_counts(model: dict, tokens: float, attention_pairs: float,
                     rows: float, n_items: int) -> dict:
    """One serving step over `tokens` real tokens in `rows` histories
    whose causal attention has `attention_pairs` (query, key) pairs
    (a history of n events: n (n + 1) / 2), scored against `n_items`.

    dense: 2 FLOPs a parameter a token, every layer in every pass;
    attention: q.k and p.v, 2 x head_dim FLOPs each a pair a head;
    gate: one hidden-size dot a token a pass; head: one hidden-size dot
    an item a row. Bytes: every layer's bfloat16 weights once a pass
    (they do not fit on-chip memory), the float32 head once."""
    layers, passes = model["num_hidden_layers"], model["total_ut_steps"]
    d = model["hidden_size"]
    a = model["num_attention_heads"] * model["head_dim"]
    apps = layers * passes
    dense = 2.0 * layer_params(model) * apps * tokens
    attention = 4.0 * a * attention_pairs * apps
    gate = 2.0 * d * passes * tokens
    head = 2.0 * d * n_items * rows
    nbytes = 2.0 * layer_params(model) * apps + 4.0 * d * n_items
    return {"flops": dense + attention + gate + head, "bytes": nbytes,
            "dense": dense, "attention": attention, "head": head}
