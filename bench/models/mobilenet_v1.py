"""MobileNet-V1 as a generic layer list.

The configuration gives the published (type, cin, cout, stride) table:
``conv`` is the 3x3 stem, ``dw`` a 3x3 depthwise conv (group = cin), ``pw``
a 1x1 pointwise conv.  Each is followed by ReLU and the activation
quantizer; then global average pooling, flatten and the float-output
classifier MatMul.  The stem keeps its own (8-bit) weight width.
"""

_KIND = {"conv": (3, 1, False), "dw": (3, 1, True), "pw": (1, 0, False)}


def layers(cfg: dict) -> list[dict]:
    act = {"relu": True, "bits": cfg["act_bits"], "signed": False,
           "scale_log2": cfg["act_scale_log2"]}
    scales = cfg["weight_scale_log2"]
    out = [dict(op="input_quant", **cfg["input_quant"])]
    for i, (kind, cin, cout, stride) in enumerate(cfg["layers"]):
        k, pad, depthwise = _KIND[kind]
        out.append({"op": "conv", "cin": cin, "cout": cout, "k": k,
                    "stride": stride, "pad": pad,
                    "group": cin if depthwise else 1,
                    "w_bits": (cfg["first_layer_weight_bits"] if i == 0
                               else cfg["weight_bits"]),
                    "w_scale_log2": scales[i], "act": dict(act)})
    out += [{"op": "gap"}, {"op": "flatten"},
            {"op": "fc", "cin": cfg["layers"][-1][2],
             "cout": cfg["classes"], "w_bits": cfg["last_layer_weight_bits"],
             "w_scale_log2": scales[len(cfg["layers"])], "act": None}]
    return out
