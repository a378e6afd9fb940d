"""Forward FLOPs per training sample of the paper's CNN.

Two 3x3 SAME convolutions, each followed by a 2x2 max-pool, then two dense
layers.  Multiply-accumulates are counted for the convolutions and the
dense layers (one MAC = 2 FLOPs); biases, ReLU, pooling and the softmax
are left out.
"""


def forward_macs(cfg) -> int:
    s, ch = cfg["image_size"], cfg["channels"]
    c1, c2 = cfg["conv_channels"]
    hid, ncls = cfg["hidden"], cfg["num_classes"]
    return (s * s * 9 * ch * c1
            + (s // 2) ** 2 * 9 * c1 * c2
            + (s // 4) ** 2 * c2 * hid
            + hid * ncls)


def forward_flops(cfg) -> int:
    return 2 * forward_macs(cfg)
