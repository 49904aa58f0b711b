"""Operations that serving one request of a latent-attention expert model
needs, from its lengths alone (beside ``serveflops.py``, which counts GPT-2's
block).  A multiply-add is two operations; element-wise passes, norms, the
rotary and the softmax are not counted."""


def mla_moe_forward_flops(*, prompt, generated, vocab_size, hidden_size,
                          num_hidden_layers, num_attention_heads,
                          kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                          v_head_dim, intermediate_size,
                          moe_intermediate_size, num_experts,
                          num_experts_per_tok, num_shared_experts,
                          first_k_dense_replace, held_experts):
    """Forward operations for one request of ``prompt`` prompt tokens that
    generates ``generated`` tokens through a cache, on a chip that holds
    ``held_experts`` of each expert layer's ``num_experts`` routed experts.

    Every token but the last generated one is fed once (``prompt + generated
    - 1`` tokens).  Per fed token and layer, multiply-adds: the query
    projection ``d x heads x (nope + rope)``, the down projection to the
    cached row ``d x (rank + rope)``, the latent's up projection ``rank x
    heads x (nope + v)`` (the absorbed step carries the query in and the
    output out through the same two matrices: the same count), the output
    projection ``heads x v x d``; a dense layer's gated feed-forward ``3 x d
    x intermediate``; an expert layer's router ``d x experts``, its shared
    experts ``3 x d x moe x shared`` and the held routed experts' terms, on
    the average ``k x held / experts`` of them a token, ``3 x d x moe`` each.
    The token at position p attends over p + 1 positions, by the *expanded*
    count: scores over ``nope + rope`` and weighted values over ``v``, per
    head, a pair and a layer (the absorbed step scores and weighs over the
    wider cached row; that is its way, not work the mathematics needs).  The
    head (``d x vocab``) is needed once for each generated token.  Divided
    by ``prompt + generated`` this is the count per processed token that
    ``serve_mfu`` multiplies with the cell's processed tokens a second."""
    d, heads = hidden_size, num_attention_heads
    fed = prompt + generated - 1
    attention = (d * heads * (qk_nope_head_dim + qk_rope_head_dim)
                 + d * (kv_lora_rank + qk_rope_head_dim)
                 + kv_lora_rank * heads * (qk_nope_head_dim + v_head_dim)
                 + heads * v_head_dim * d)
    dense_layers = min(first_k_dense_replace, num_hidden_layers)
    expert_layers = num_hidden_layers - dense_layers
    expert = 3 * d * moe_intermediate_size
    expert_layer = (d * num_experts + num_shared_experts * expert
                    + num_experts_per_tok * held_experts / num_experts * expert)
    per_token = (num_hidden_layers * attention
                 + dense_layers * 3 * d * intermediate_size
                 + expert_layers * expert_layer)
    pairs = fed * (fed + 1) / 2.0
    per_pair = heads * (qk_nope_head_dim + qk_rope_head_dim + v_head_dim)
    return (2.0 * per_token * fed + 2.0 * d * vocab_size * generated
            + 2.0 * num_hidden_layers * per_pair * pairs)
