"""Operations and bytes the algorithms need, computed from shapes alone.

The yardstick's arithmetic: nothing here is read from the program.  A model's
training operations are those the forward and backward passes require
(2 per multiply-add, backward twice the forward); recomputed operations and
the embedding lookup do not count.  A kernel's cost is what the algorithm
needs for one call at its shape, not what an implementation happens to do.
"""


# -- the OPT-style decoder -------------------------------------------------
def lm_layer_matrix_params(m):
    d, f = m["d_model"], m["d_ff"]
    return 4 * d * d + 2 * d * f


def lm_matrix_params(m):
    """Parameters that take part in matrix products: every block's four
    attention projections and two FFN matrices, and the tied output head
    (the lookup through the same matrix is no product and is not counted)."""
    return m["n_layers"] * lm_layer_matrix_params(m) + m["vocab"] * m["d_model"]


def lm_train_flops_per_token(config, traffic):
    """6 x matrix parameters, plus causal attention: QK^T and PV are
    2 * S * d multiply-adds a token a layer at full length and half that under
    the causal mask, so 2 * S * d operations forward and 6 * S * d with the
    backward pass."""
    m = config["program"]
    s = int(traffic["seq_len"])
    return 6 * lm_matrix_params(m) + 6 * s * m["d_model"] * m["n_layers"]


def flash_attention_train(config, traffic):
    """One layer's flash attention, forward and backward, for one step:
    {"flops", "bytes"}.  Forward is two products (QK^T, PV), backward five
    (S again, dP, dV, dQ, dK: the flash algorithm's own recomputation of S is
    part of it), each 2 * S^2 * dh operations a head and half under the causal
    mask.  Bytes: q, k, v read and o written forward; q, k, v, o, do read and
    dq, dk, dv written backward, in the compute type; the row statistics in
    float32."""
    m = config["program"]
    b, s = int(traffic["batch"]), int(traffic["seq_len"])
    h = m["n_heads"]
    dh = m["d_model"] // h
    width = 2 if m["dtype"] == "bfloat16" else 4
    per_product = 2 * s * s * dh * 0.5
    flops = b * h * 7 * per_product
    tensors = 4 + 8
    bytes_ = b * h * (tensors * s * dh * width + 3 * s * 4)
    return {"flops": flops, "bytes": bytes_}


# -- ResNet (He et al. 2016, bottleneck, ImageNet) ----------------------------
def _conv_macs(h, w, cin, cout, k, stride):
    ho, wo = -(-h // stride), -(-w // stride)
    return ho * wo * cin * cout * k * k, ho, wo


def resnet_forward_macs(net):
    """Multiply-adds of one image's forward pass through the convolutions and
    the classifier, for the bottleneck ImageNet network of ``num_layers`` 50,
    101 or 152: 7x7/2 stem, 3x3/2 max pool, four stages of units
    (1x1, 3x3 carrying the stride, 1x1, and a 1x1 projection where the shape
    changes), global pool, fully connected."""
    units = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}[net["num_layers"]]
    _c, h, w = net["image_shape"]
    total, h, w = _conv_macs(h, w, 3, 64, 7, 2)
    h, w = -(-h // 2), -(-w // 2)                  # max pool
    cin = 64
    for stage, n in enumerate(units):
        cout = 256 * 2 ** stage
        mid = cout // 4
        for u in range(n):
            stride = 2 if (u == 0 and stage > 0) else 1
            a, _, _ = _conv_macs(h, w, cin, mid, 1, 1)
            b, ho, wo = _conv_macs(h, w, mid, mid, 3, stride)
            c, _, _ = _conv_macs(ho, wo, mid, cout, 1, 1)
            total += a + b + c
            if u == 0:
                p, _, _ = _conv_macs(h, w, cin, cout, 1, stride)
                total += p
            h, w, cin = ho, wo, cout
    return total + cin * net["num_classes"]


def resnet_train_flops_per_image(config, traffic):
    """Forward and backward: 2 operations a multiply-add, backward twice the
    forward (the first convolution's input gradient, which nothing needs, is
    0.2 % of it and is not taken off)."""
    return 6 * resnet_forward_macs(config["network"])


COSTS = {
    "lm_train_flops_per_token": lm_train_flops_per_token,
    "flash_attention_train": flash_attention_train,
    "resnet_train_flops_per_image": resnet_train_flops_per_image,
}
