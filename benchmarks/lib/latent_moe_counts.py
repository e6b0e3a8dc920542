"""Operations and bytes a latent-attention mixture-of-experts serving
step NEEDS, from its shapes and from what the device program counted
(as lib/seq_counts.py has them for the looped decoder): the model FLOPs
of the step's REAL tokens, the experts by the assignments that fell
here, attention by its causal pairs; padding, a kernel's masked half
tiles and a grouped matmul's part-filled tiles count as time and never
as work."""

from __future__ import annotations


def routed_layers(model: dict) -> int:
    return sum(1 for i in range(model["num_hidden_layers"])
               if model["first_layer"] + i >= model["first_k_dense_replace"])


def latent_projection_params(model: dict) -> int:
    """Matrix parameters of one layer's attention: the two down
    projections, the two up projections and the output's."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    dn, dr, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                  model["v_head_dim"])
    return (d * model["q_lora_rank"] + model["q_lora_rank"] * h * (dn + dr)
            + d * (model["kv_lora_rank"] + dr)
            + model["kv_lora_rank"] * h * (dn + dv) + h * dv * d)


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def attention_counts(model: dict, pairs: float, tokens: float) -> dict:
    """The attention kernel over `pairs` causal (query, key) pairs, all
    layers together (the device's own count is): q.k over d_qk and p.v
    over d_v, 2 FLOPs each a pair a head. Bytes: q, k, v read and the
    output written once a layer, bfloat16 (the rotary key one head)."""
    h = model["num_attention_heads"]
    dqk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    dv = model["v_head_dim"]
    layers = model["num_hidden_layers"]
    nbytes = 2.0 * tokens * layers * (
        h * dqk + h * model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
        + 2 * h * dv)
    return {"flops": 2.0 * h * (dqk + dv) * pairs, "bytes": nbytes}


def expert_matmul_counts(model: dict, assignments_here: float,
                         layer_passes: float) -> dict:
    """The grouped matmuls of the held experts over the rows routed to
    them: 2 FLOPs a parameter a row. Bytes: the held experts' bfloat16
    weights once a pass of a layer, the rows read (hidden) and written
    (hidden, float32) once."""
    d = model["hidden_size"]
    held = model["experts_held"]
    return {"flops": 2.0 * expert_params(model) * assignments_here,
            "bytes": 2.0 * expert_params(model) * held * layer_passes
            + assignments_here * d * (2 + 4)}


def step_counts(model: dict, tokens: float, pairs: float,
                assignments_here: float, rows: float, n_items: int) -> dict:
    """One serving step (or many: the counts add) over `tokens` real
    tokens with `pairs` causal pairs (all layers), `assignments_here`
    (token, choice) pairs on held experts (all routed layers), `rows`
    histories scored against `n_items`."""
    d = model["hidden_size"]
    layers, routed = model["num_hidden_layers"], routed_layers(model)
    dense = layers - routed
    shared = (3 * d * model["moe_intermediate_size"]
              * model["n_shared_experts"])
    per_token = (latent_projection_params(model) * layers
                 + 3 * d * model["intermediate_size"] * dense
                 + (shared + d * model["n_routed_experts"]) * routed)
    parts = {
        "dense": 2.0 * per_token * tokens,
        "attention": attention_counts(model, pairs, tokens)["flops"],
        "experts": 2.0 * expert_params(model) * assignments_here,
        "head": 2.0 * d * n_items * rows,
    }
    return {"flops": sum(parts.values()), **parts}
