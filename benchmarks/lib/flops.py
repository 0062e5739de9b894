"""Operations and bytes a step needs, from shapes alone.

Model FLOPs in the scaling-book sense: what the forward and backward
passes require, recomputation not counted, embedding gathers excluded
(they do no matrix work).  Each function has a CPU test against a hand
count at a tiny shape (benchmarks/tests/test_flops.py).
"""


def transformer_matmul_params(n_layer, d_model, d_inner, vocab):
    """Parameters of models/transformer.py that sit in matrix
    multiplications: per encoder layer qkv (d*3d) + o (d*d) + ffn
    (2*d*d_inner); per decoder layer self (4 d^2) + cross q (d^2),
    kv (2 d^2), o (d^2) + ffn; the vocabulary projection d*vocab.
    Biases, LayerNorm scales and the two embedding tables are left out.
    """
    d, f = d_model, d_inner
    enc = 4 * d * d + 2 * d * f
    dec = 8 * d * d + 2 * d * f
    return n_layer * (enc + dec) + d * vocab


def transformer_train_flops_per_token(n_layer, d_model, d_inner, vocab, seq):
    """6*P_matmul + 12*T*d_model per attention block, three blocks a
    layer pair (encoder self, decoder self, decoder cross): the score and
    context products are 4*T*d forward and twice that backward.  The
    causal half of decoder self-attention is counted in full, as
    tools/perflab.py:163 and bench.py do."""
    p = transformer_matmul_params(n_layer, d_model, d_inner, vocab)
    return 6.0 * p + 12.0 * seq * d_model * (3 * n_layer)


# ResNet of arXiv:1512.03385, counted from the layer shapes with a
# multiply-add as TWO operations, which is how the chip's 197 TFLOP/s is
# counted.  ResNet-50 at 224x224 gives 7.72e9 per image forward, that is
# 3.86e9 multiply-adds: the paper's Table 1 says "3.8e9 FLOPs" and means
# multiply-adds.  (bench.py's 3 * 4.1e9 counts a multiply-add once and so
# reads half the utilization; it is not used here.)
def resnet_forward_flops_per_image(depth, side, classes):
    stages = {18: ([2, 2, 2, 1], False), 34: ([3, 4, 6, 3], False),
              50: ([3, 4, 6, 3], True), 101: ([3, 4, 23, 3], True),
              152: ([3, 8, 36, 3], True)}[depth]
    counts, bottleneck = stages

    def conv(hw_out, c_in, c_out, k):
        return 2.0 * hw_out * hw_out * c_in * c_out * k * k

    hw = side // 2                      # conv1: 7x7 stride 2
    total = conv(hw, 3, 64, 7)
    hw = hw // 2                        # max pool stride 2
    c_in = 64
    for stage, (n, width) in enumerate(zip(counts, (64, 128, 256, 512))):
        for block in range(n):
            stride = 2 if (block == 0 and stage > 0) else 1
            hw_out = hw // stride
            if bottleneck:
                # models/resnet.py puts the stride on the first 1x1
                c_out = width * 4
                total += conv(hw_out, c_in, width, 1)
                total += conv(hw_out, width, width, 3)
                total += conv(hw_out, width, c_out, 1)
            else:
                c_out = width
                total += conv(hw_out, c_in, width, 3)
                total += conv(hw_out, width, width, 3)
            if c_in != c_out:
                total += conv(hw_out, c_in, c_out, 1)
            c_in, hw = c_out, hw_out
    return total + 2.0 * c_in * classes


def resnet_train_flops_per_image(depth, side, classes):
    """Forward plus backward: the gradient with respect to the input and
    to the filter each cost one forward, so 3x."""
    return 3.0 * resnet_forward_flops_per_image(depth, side, classes)


def llama_block_params(d_model, n_head, n_kv_head, head_dim, d_ffn):
    """One decoder layer's matrix parameters (q, k, v, o, gate, up, down)."""
    return (d_model * n_head * head_dim + 2 * d_model * n_kv_head * head_dim
            + n_head * head_dim * d_model + 3 * d_model * d_ffn)


def decode_step_bytes(n_layer, d_model, n_head, n_kv_head, head_dim, d_ffn,
                      vocab, weight_bytes, kv_bytes, live_kv_tokens,
                      active_rows):
    """The least bytes ONE decode step (one token for every active slot)
    must read from HBM: every layer's matrices and the output head once,
    the embedding rows of the fed tokens, and the K and V rows of every
    live cached token.  Norm scales and activations are left out (under
    0.01 %).  What the program reads beyond this, such as dead pages of
    the pool, is what the roofline share exposes."""
    weights = (n_layer * llama_block_params(d_model, n_head, n_kv_head,
                                            head_dim, d_ffn)
               + d_model * vocab) * weight_bytes
    embed = active_rows * d_model * weight_bytes
    kv = 2.0 * n_layer * n_kv_head * head_dim * kv_bytes * live_kv_tokens
    return weights + embed + kv


def decode_step_flops(n_layer, d_model, n_head, n_kv_head, head_dim, d_ffn,
                      vocab, live_kv_tokens, active_rows):
    """Matrix operations of one decode step: 2 per parameter per active
    row, plus scores and context over the live cache (4 * n_head *
    head_dim per cached token per layer)."""
    p = n_layer * llama_block_params(d_model, n_head, n_kv_head, head_dim,
                                     d_ffn) + d_model * vocab
    return 2.0 * p * active_rows + 4.0 * n_layer * n_head * head_dim \
        * live_kv_tokens
