"""Operations and bytes that the algorithm needs, from shapes alone.

The yardstick's arithmetic: a later PR may not change it.  A multiply-add is
two operations.  Forward and backward as the mathematics requires them;
recomputed operations are never counted, and neither are the element-wise
passes (activations, bias, pooling, normalisation, softmax), which are
O(activations) and under 1% of the terms below.  A configuration's file names
its function under ``"flops"``; a new kind of model adds a module of its own.
"""


def _layer_forward(spec):
    kind = spec[0]
    if kind == "conv":  # (conv, h_out, w_out, cout, k, cin), SAME padding
        _, h, w, cout, k, cin = spec
        return 2.0 * h * w * cout * k * k * cin
    if kind == "dense":  # (dense, fin, fout)
        _, fin, fout = spec
        return 2.0 * fin * fout
    raise ValueError(f"unknown layer spec {spec!r}")


def layer_stack_train_flops(*, layers):
    """Per row: forward + weight gradient + input gradient of every layer
    (copied from ``bench.py::analytic_train_flops_per_sample``), except that
    the first layer's input gradient is not needed and not counted."""
    forward = [_layer_forward(tuple(spec)) for spec in layers]
    return 3.0 * sum(forward) - forward[0]


def transformer_lm_train_flops(*, vocab_size, dim, heads, num_layers, seq,
                               mlp_ratio=4):
    """Per token of a causal decoder trained on sequences of ``seq`` tokens:
    6 x the parameters that a token is multiplied with (per layer q, k, v and
    output projections 4 d^2 and the MLP 2 * ratio * d^2; the untied head
    d * V; embeddings are lookups) plus causal attention.  A query at position
    i needs scores and weighted values over i + 1 keys: over a sequence that
    is seq * (seq + 1) / 2 pairs, 4 * head_dim operations each per head
    forward, twice that backward."""
    del heads  # heads * head_dim = dim: the count does not depend on the split
    matmul_params = num_layers * (4 + 2 * mlp_ratio) * dim * dim + dim * vocab_size
    pairs_per_token = (seq + 1) / 2.0
    attention_forward = num_layers * 4.0 * dim * pairs_per_token
    return 6.0 * matmul_params + 3.0 * attention_forward


def flash_attention_cost(*, batch, seq, heads, head_dim, dtype_bytes=2):
    """Operations and HBM bytes that causal attention needs for ``batch``
    sequences, forward and backward, as the three kernels divide them.
    Forward: scores and weighted values, 2 matmuls over seq * (seq + 1) / 2
    pairs; reads q, k, v and writes o.  Backward: dv, dp, dq, dk, 4 matmuls
    (the kernels' recomputation of the scores is not counted); reads q, k, v,
    o, do and writes dq, dk, dv.  The float32 row statistics (log-sum-exp,
    delta) are seq * heads * 4 bytes each and are counted."""
    pairs = batch * heads * seq * (seq + 1) / 2.0
    matmul = 2.0 * pairs * head_dim
    tensor = batch * seq * heads * head_dim * dtype_bytes
    rowstat = batch * seq * heads * 4.0
    return {"forward": {"flops": 2 * matmul, "bytes": 4 * tensor + rowstat},
            "backward": {"flops": 4 * matmul,
                         "bytes": 8 * tensor + 2 * rowstat}}
