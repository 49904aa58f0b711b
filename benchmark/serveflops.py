"""Operations that serving one request needs, from its lengths alone.

The yardstick's arithmetic for the serving cells, beside ``flops.py`` (which
counts training).  A multiply-add is two operations; element-wise passes are
not counted.
"""


def transformer_lm_forward_flops(*, vocab_size, dim, heads, num_layers,
                                 prompt, generated, mlp_ratio=4):
    """Forward operations for one request of ``prompt`` prompt tokens that
    generates ``generated`` tokens through a cache.  Every token but the last
    generated one is fed once: ``prompt + generated - 1`` tokens through the
    blocks' matrices (per layer q, k, v and output projections 4 d^2 and the
    feed-forward 2 * ratio * d^2).  The token at position p attends over
    p + 1 keys: scores and weighted values, 4 * dim operations a pair and a
    layer.  The head (d * V) is needed once for each generated token, not for
    the prompt's positions.  Divided by ``prompt + generated`` this is the
    count per processed token that ``serve_mfu`` multiplies with the cell's
    processed tokens a second."""
    del heads  # heads * head_dim = dim: the count does not depend on the split
    fed = prompt + generated - 1
    block = num_layers * (4 + 2 * mlp_ratio) * dim * dim
    pairs = fed * (fed + 1) / 2.0
    return (2.0 * block * fed + 2.0 * dim * vocab_size * generated
            + num_layers * 4.0 * dim * pairs)
