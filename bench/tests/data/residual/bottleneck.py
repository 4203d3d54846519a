"""A ResNet of bottleneck blocks as a generic layer list.

The configuration gives the stem conv and its padded max-pool, then
blocks of (cin, mid, cout, stride): 1x1 -> 3x3/stride -> 1x1 convs on the
branch, a 1x1/stride projection on the shortcut where the block changes
the width or the stride, and an ``add`` of the two with ReLU and the
activation quantizer.  The convs inside a block use ``weight_bits``; the
stem and the classifier keep their own widths.  The branch and the
projection end in a signed quantizer, so that the add joins two
quantized tensors of one scale.  Global average pooling, flatten and the
float-output classifier close the list.
"""


def _conv(cin, cout, k, stride, pad, w_bits, w_scale_log2, act):
    return {"op": "conv", "cin": cin, "cout": cout, "k": k, "stride": stride,
            "pad": pad, "group": 1, "w_bits": w_bits,
            "w_scale_log2": w_scale_log2, "act": act}


def layers(cfg: dict) -> list[dict]:
    bits, scale = cfg["act_bits"], cfg["act_scale_log2"]
    relu = {"relu": True, "bits": bits, "signed": False, "scale_log2": scale}
    signed = {"relu": False, "bits": bits, "signed": True,
              "scale_log2": scale}
    stem = cfg["stem"]
    out = [dict(op="input_quant", **cfg["input_quant"]),
           _conv(cfg["input_shape"][0], stem["cout"], stem["k"],
                 stem["stride"], stem["pad"], stem["w_bits"],
                 stem["w_scale_log2"], relu),
           dict(op="maxpool", **cfg["stem_pool"])]
    wb = cfg["weight_bits"]
    for cin, mid, cout, stride, scales in cfg["blocks"]:
        x = len(out) - 1                           # the block's input
        out += [_conv(cin, mid, 1, 1, 0, wb, scales[0], relu),
                _conv(mid, mid, 3, stride, 1, wb, scales[1], relu),
                _conv(mid, cout, 1, 1, 0, wb, scales[2], signed)]
        branch, shortcut = len(out) - 1, x
        if cin != cout or stride != 1:
            out.append(dict(_conv(cin, cout, 1, stride, 0, wb, scales[3],
                                  signed), **{"in": [x]}))
            shortcut = len(out) - 1
        out.append({"op": "add", "in": [branch, shortcut], "act": relu})
    out += [{"op": "gap"}, {"op": "flatten"},
            {"op": "fc", "cin": cfg["blocks"][-1][2], "cout": cfg["classes"],
             "w_bits": cfg["last_layer_weight_bits"],
             "w_scale_log2": cfg["last_layer_weight_scale_log2"],
             "act": None}]
    return out
