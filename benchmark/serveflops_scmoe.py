"""Operations that serving one request of a shortcut-expert model of double
layers needs, from its lengths alone (beside ``serveflops.py`` and
``serveflops_mla_moe.py``).  A multiply-add is two operations; element-wise
passes, norms, the rotary, the softmaxes and the zero-compute experts'
``weight x input`` are not counted."""


def scmoe_forward_flops(*, prompt, generated, vocab_size, hidden_size,
                        num_layers, num_attention_heads, kv_lora_rank,
                        q_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
                        v_head_dim, ffn_hidden_size, expert_ffn_hidden_size,
                        n_routed_experts, zero_expert_num, moe_topk,
                        held_experts):
    """Forward operations for one request of ``prompt`` prompt tokens that
    generates ``generated`` tokens through a cache, on a chip that holds
    ``held_experts`` of each double layer's ``n_routed_experts`` routed
    experts.

    Every token but the last generated one is fed once (``prompt + generated
    - 1`` tokens).  Per fed token and double layer, multiply-adds: **two**
    latent attentions, each the query's low-rank path ``d x q_rank + q_rank x
    heads x (nope + rope)``, the down projection to the cached row ``d x
    (rank + rope)``, the latent's up projection ``rank x heads x (nope + v)``
    (the absorbed step carries the query in and the output out through the
    same two matrices: the same count) and the output projection ``heads x v
    x d``; **two** dense gated feed-forwards ``3 x d x ffn``; the router ``d
    x (routed + zero-compute)``; and the held routed experts' terms **at
    their even-routing expectation**, ``moe_topk x held / (routed +
    zero-compute)`` of them a token (12 x 16 / 768 = 0.25 in the cell), ``3 x
    d x expert_ffn`` each: the count does not follow a run's actual routing.
    The token at position p attends over p + 1 positions in both caches, by
    the *expanded* count: scores over ``nope + rope`` and weighted values
    over ``v``, per head, a pair and an attention.  The head (``d x vocab``)
    is needed once for each generated token.  Divided by ``prompt +
    generated`` this is the count per processed token that ``serve_mfu``
    multiplies with the cell's processed tokens a second."""
    d, heads = hidden_size, num_attention_heads
    fed = prompt + generated - 1
    attention = (d * q_lora_rank
                 + q_lora_rank * heads * (qk_nope_head_dim + qk_rope_head_dim)
                 + d * (kv_lora_rank + qk_rope_head_dim)
                 + kv_lora_rank * heads * (qk_nope_head_dim + v_head_dim)
                 + heads * v_head_dim * d)
    outputs = n_routed_experts + zero_expert_num
    branch = (d * outputs + moe_topk * held_experts / outputs
              * 3 * d * expert_ffn_hidden_size)
    per_token = num_layers * (2 * attention + 2 * 3 * d * ffn_hidden_size
                              + branch)
    pairs = fed * (fed + 1) / 2.0
    per_pair = 2 * heads * (qk_nope_head_dim + qk_rope_head_dim + v_head_dim)
    return (2.0 * per_token * fed + 2.0 * d * vocab_size * generated
            + 2.0 * num_layers * per_pair * pairs)
