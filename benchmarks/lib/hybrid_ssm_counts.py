"""Operations and bytes a hybrid state-space serving step NEEDS, from
its shapes and from what the device program counted (as
lib/latent_moe_counts.py has them for the latent decoder): the model
FLOPs of the step's REAL tokens, the scan by the chunks that held a real
token, attention by its causal pairs. The scan's count is of the SUM the
published description states (the recurrence), whatever implements it:
the chunked form's extra matmuls, padding, masked halves of a tile count
as time and never as work."""

from __future__ import annotations

from .hybrid_ssm_reference import conv_dim, d_inner


def kinds(model: dict) -> tuple[int, int]:
    """(Mamba layers, attention layers)."""
    n_mamba = sum(1 for t in model["layer_types"] if t == "mamba")
    return n_mamba, len(model["layer_types"]) - n_mamba


def matrix_params(model: dict) -> int:
    """Parameters of every matrix a token passes (the tied table's
    lookup is no matmul; the head is counted by its rows)."""
    D, F = model["hidden_size"], model["shared_intermediate_size"]
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    hd = D // H
    n_mamba, n_attention = kinds(model)
    mamba = D * (d_inner(model) + conv_dim(model) + model["mamba_n_heads"]
                 ) + d_inner(model) * D
    attention = D * (H + 2 * KV) * hd + H * hd * D
    return (n_mamba * mamba + n_attention * attention
            + (n_mamba + n_attention) * 3 * D * F)


def scan_counts(model: dict, chunks: float) -> dict:
    """The convolution and the selective scan over ``chunks`` chunks of
    ``mamba_chunk_size`` tokens (all Mamba layers together: the device's
    own count is). A token a head: the state [d_head, N] decayed, the
    outer product added (3 FLOPs an element) and read out against C (2);
    the convolution 2 FLOPs a tap a channel. Bytes: what the two scopes
    take and give, float32 as the configuration states them: the
    convolution's input (d_inner + 2 N) and dt in, y out."""
    tokens = chunks * model["mamba_chunk_size"]
    state = d_inner(model) * model["mamba_d_state"]
    flops = tokens * (5.0 * state
                      + 2.0 * model["mamba_d_conv"] * conv_dim(model))
    nbytes = 4.0 * tokens * (conv_dim(model) + model["mamba_n_heads"]
                             + d_inner(model))
    return {"flops": flops, "bytes": nbytes}


def attention_counts(model: dict, pairs: float, tokens: float) -> dict:
    """The attention kernel over ``pairs`` causal (query, key) pairs, the
    attention layers together (the device's own count is): q.k and p.v
    over heads of head_dim, 2 FLOPs each a pair a query head. Bytes: q
    and the output once a query head, k and v once a key/value head,
    bfloat16, a layer."""
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model["hidden_size"] // H
    _m, n_attention = kinds(model)
    return {"flops": 4.0 * H * hd * pairs,
            "bytes": 2.0 * tokens * n_attention * (2 * H + 2 * KV) * hd}


def step_counts(model: dict, tokens: float, pairs: float, chunks: float,
                rows: float, n_items: int) -> dict:
    """One serving step (or many: the counts add) over ``tokens`` real
    tokens with ``pairs`` causal pairs (attention layers together),
    ``chunks`` live scan chunks (Mamba layers together), ``rows``
    histories scored against ``n_items``."""
    parts = {
        "dense": 2.0 * matrix_params(model) * tokens,
        "scan": scan_counts(model, chunks)["flops"],
        "attention": attention_counts(model, pairs, tokens)["flops"],
        "head": 2.0 * model["hidden_size"] * n_items * rows,
    }
    return {"flops": sum(parts.values()), **parts}
