"""Operations that serving one request of a model of sliding-window and
global grouped-query layers over expert layers needs, from its lengths alone
(beside ``serveflops.py``, ``serveflops_mla_moe.py`` and
``serveflops_scmoe.py``).  A multiply-add is two operations; element-wise
passes, norms, the rotary, the softmaxes and the sinks are not counted."""


def _attended(fed, window):
    """Query-key pairs of ``fed`` tokens that each attend ``min(p + 1,
    window)`` positions (``window`` None: ``p + 1``)."""
    if window is None or fed <= window:
        return fed * (fed + 1) / 2.0
    return window * (window + 1) / 2.0 + (fed - window) * float(window)


def window_moe_forward_flops(*, prompt, generated, vocab_size, hidden_size,
                             hybrid_layer_pattern, moe_layer_freq,
                             num_attention_heads, num_key_value_heads,
                             head_dim, v_head_dim, swa_num_attention_heads,
                             swa_num_key_value_heads, swa_head_dim,
                             swa_v_head_dim, sliding_window,
                             intermediate_size, moe_intermediate_size,
                             n_routed_experts, num_experts_per_tok,
                             held_experts):
    """Forward operations for one request of ``prompt`` prompt tokens that
    generates ``generated`` tokens through a cache, on a chip that holds
    ``held_experts`` of each expert layer's ``n_routed_experts``.

    Every token but the last generated one is fed once (``prompt + generated
    - 1`` tokens).  Per fed token and layer of its kind (``hybrid_layer_
    pattern``: 0 global, 1 sliding, each with its own heads and widths),
    multiply-adds: the projections ``d x heads x head_dim`` (q), ``d x
    kv_heads x (head_dim + v_dim)`` (k and v) and ``heads x v_dim x d`` (o);
    a dense layer's ``3 x d x intermediate``; an expert layer's router ``d x
    n_routed_experts`` and its held experts **at their even-routing
    expectation**, ``num_experts_per_tok x held / n_routed_experts`` of them
    a token (8 x 16 / 256 = 0.5 in the cell), ``3 x d x moe_width`` each:
    the count does not follow a run's actual routing.  The token at
    position p attends over ``p + 1`` positions in a global layer and
    ``min(p + 1, sliding_window)`` in a sliding one, ``heads x (head_dim +
    v_dim)`` a pair.  The head (``d x vocab``) is needed once for each
    generated token.  Divided by ``prompt + generated`` this is the count
    per processed token that ``serve_mfu`` multiplies with the cell's
    processed tokens a second."""
    d = hidden_size
    fed = prompt + generated - 1
    kinds = {0: (num_attention_heads, num_key_value_heads, head_dim,
                 v_head_dim, None),
             1: (swa_num_attention_heads, swa_num_key_value_heads,
                 swa_head_dim, swa_v_head_dim, sliding_window)}
    per_token = pairs = 0.0
    for kind, expert in zip(hybrid_layer_pattern, moe_layer_freq):
        heads, kv_heads, width, v_width, window = kinds[kind]
        per_token += (d * heads * width + d * kv_heads * (width + v_width)
                      + heads * v_width * d)
        if expert:
            per_token += (d * n_routed_experts
                          + num_experts_per_tok * held_experts
                          / n_routed_experts * 3 * d * moe_intermediate_size)
        else:
            per_token += 3 * d * intermediate_size
        pairs += heads * (width + v_width) * _attended(fed, window)
    return 2.0 * per_token * fed + 2.0 * d * vocab_size * generated + 2.0 * pairs
