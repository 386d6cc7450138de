"""Family ``resnet_v1``: the bottleneck ResNet of He et al. 2015, table 1
(strides in the first 1x1 convolution of a down-sampling block).  FLOPs
of one training step from the configuration's sizes."""
from flops import conv2d


def _out(size, k, stride, pad):
    return (size + 2 * pad - k) // stride + 1


def forward_flops(config, batch):
    """Forward FLOPs of the bottleneck ResNet of ``config`` on ``batch``
    images, and those of the stem convolution alone."""
    c, h, w = config["input"]["shape"]
    ch = config["channels"]
    h, w = _out(h, 7, 2, 3), _out(w, 7, 2, 3)
    stem = conv2d(batch, c, ch[0], h, w, 7, 7)
    total = stem
    h, w = _out(h, 3, 2, 1), _out(w, 3, 2, 1)
    cin = ch[0]
    for s, (n_blocks, cout) in enumerate(zip(config["layers"], ch[1:]), 1):
        mid = cout // 4
        for b in range(n_blocks):
            stride = 2 if (b == 0 and s > 1) else 1
            h2, w2 = _out(h, 1, stride, 0), _out(w, 1, stride, 0)
            total += conv2d(batch, cin, mid, h2, w2, 1, 1)
            total += conv2d(batch, mid, mid, h2, w2, 3, 3)
            total += conv2d(batch, mid, cout, h2, w2, 1, 1)
            if b == 0 and cin != cout:
                total += conv2d(batch, cin, cout, h2, w2, 1, 1)
            h, w, cin = h2, w2, cout
    total += 2.0 * batch * ch[-1] * config["classes"]
    return total, stem


def train_step_flops(config, batch):
    total, stem = forward_flops(config, batch)
    return 3.0 * total - stem       # the image needs no gradient
