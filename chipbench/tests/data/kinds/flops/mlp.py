"""Forward FLOPs per training sample of the paper's MLP: three dense
layers' multiply-accumulates (one MAC = 2 FLOPs)."""


def forward_flops(cfg) -> int:
    d_in = cfg["image_size"] ** 2 * cfg["channels"]
    hid = cfg["hidden"]
    return 2 * (d_in * hid + hid * hid + hid * cfg["num_classes"])
