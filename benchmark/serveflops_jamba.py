"""Operations that serving one request of a model of Mamba and attention
layers needs, from its lengths alone (beside ``serveflops.py`` and the
expert models' counts), and the operations and bytes of one call of the
decode step's selective-scan kernel (``ssm_step``) and of the prefill's
(``ssm_scan``).  A multiply-add is two operations; norms, the softmaxes, the
activations and the softplus are not counted; the scan's element-wise work
is (below)."""

#: operations of the selective scan a position, channel and state: ``Delta
#: x A``, its ``exp``, the decay times the state, ``B x (Delta x)``, the sum,
#: and ``C x h`` added into ``y``
SCAN_OPS = 7
#: bytes of a float32
F32 = 4


def _is_attention(index, period, offset):
    return index % period == offset


def jamba_forward_flops(*, prompt, generated, vocab_size, hidden_size,
                        num_hidden_layers, attn_layer_period,
                        attn_layer_offset, num_attention_heads,
                        num_key_value_heads, intermediate_size,
                        mamba_d_state, mamba_d_conv, mamba_expand,
                        mamba_dt_rank):
    """Forward operations for one request of ``prompt`` prompt tokens that
    generates ``generated`` tokens through a cache.

    Every token but the last generated one is fed once (``prompt + generated
    - 1`` tokens).  Per fed token, multiply-adds: in every layer the dense
    feed-forward's ``3 x d x intermediate``; in a Mamba layer (``d_inner =
    mamba_expand x d``) the projections ``d x 2 d_inner`` (in),
    ``d_inner x (dt_rank + 2 d_state)`` (x), ``dt_rank x d_inner`` (dt) and
    ``d_inner x d`` (out), and the convolution's ``d_conv x d_inner``; in an
    attention layer ``d x heads x head_dim`` (q), ``2 d x kv_heads x
    head_dim`` (k and v) and ``heads x head_dim x d`` (o).  The selective
    scan: ``SCAN_OPS`` operations a fed token, channel and state (7 x 5,120 x
    16 = 573,440 a token and Mamba layer, some 0.2% of the layer's
    products).  The token at position p attends over ``p + 1`` positions in
    an attention layer, ``heads x 2 head_dim`` a pair.  The head (the
    embedding transposed, ``d x vocab``) is needed once for each generated
    token.  Divided by ``prompt + generated`` this is the count per processed
    token that ``serve_mfu`` multiplies with the cell's processed tokens a
    second."""
    d = hidden_size
    di = mamba_expand * d
    head_dim = d // num_attention_heads
    fed = prompt + generated - 1
    attention = sum(_is_attention(i, attn_layer_period, attn_layer_offset)
                    for i in range(num_hidden_layers))
    mamba = num_hidden_layers - attention
    mixer = (d * 2 * di + di * (mamba_dt_rank + 2 * mamba_d_state)
             + mamba_dt_rank * di + di * d + mamba_d_conv * di)
    attend = (d * num_attention_heads * head_dim
              + 2 * d * num_key_value_heads * head_dim
              + num_attention_heads * head_dim * d)
    per_token = (num_hidden_layers * 3 * d * intermediate_size
                 + mamba * mixer + attention * attend)
    pairs = attention * num_attention_heads * 2 * head_dim * fed * (fed + 1) / 2.0
    scan = mamba * SCAN_OPS * di * mamba_d_state * fed
    return (2.0 * per_token * fed + 2.0 * d * vocab_size * generated
            + 2.0 * pairs + scan)


def ssm_step_cost(*, slots, d_inner, d_state):
    """One call of ``ssm_step`` (one Mamba layer of one decode step over
    every slot): ``{"flops", "bytes"}``.  Operations: ``SCAN_OPS`` a slot,
    channel and state.  Bytes, all float32: every slot's state read and
    written (the kernel reads and writes back every slot, a slot that is not
    live as it was), the inputs ``x`` and ``Delta`` and the output ``y`` a
    slot and channel, ``B`` and ``C`` a slot and state, ``A`` and ``D``
    once."""
    state = slots * d_state * d_inner
    return {"flops": float(SCAN_OPS * state),
            "bytes": float(F32 * (2 * state + 3 * slots * d_inner
                                  + 2 * slots * d_state
                                  + d_state * d_inner + d_inner))}


def ssm_scan_cost(*, positions, d_inner, d_state):
    """One call of ``ssm_scan`` (one Mamba layer of one prefill chunk of
    ``positions``): ``{"flops", "bytes"}``, all float32: ``x``, ``Delta``
    and ``y`` a position and channel, ``B`` and ``C`` a position and state,
    ``A`` and ``D`` once and the final state written.  Not read by any metric
    yet: the trace's reducer counts a kernel's time but not its calls, and a
    prefill's width varies (``PERF.md``, section 7)."""
    return {"flops": float(SCAN_OPS * positions * d_inner * d_state),
            "bytes": float(F32 * (3 * positions * d_inner
                                  + 2 * positions * d_state
                                  + 2 * d_state * d_inner + d_inner))}
