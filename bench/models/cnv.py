"""FINN's CNV (VGG-like CIFAR-10 network) as a generic layer list.

The configuration gives the conv table (``[cin, cout]`` pairs and ``"M"``
for a 2x2 max-pool), the FC table, the bit widths and one power-of-two
weight scale per MAC layer.  Every conv and the two hidden FCs end in the
configured activation quantizer; the last FC gives float logits.
"""


def layers(cfg: dict) -> list[dict]:
    act = {"relu": cfg["act_relu"], "bits": cfg["act_bits"],
           "signed": cfg["act_signed"], "scale_log2": cfg["act_scale_log2"]}
    scales = iter(cfg["weight_scale_log2"])
    out = [dict(op="input_quant", **cfg["input_quant"])]
    for spec in cfg["convs"]:
        if spec == "M":
            out.append({"op": "maxpool", "k": 2, "stride": 2})
            continue
        cin, cout = spec
        out.append({"op": "conv", "cin": cin, "cout": cout,
                    "k": cfg["kernel"], "stride": 1, "pad": cfg["pad"],
                    "group": 1, "w_bits": cfg["weight_bits"],
                    "w_scale_log2": next(scales), "act": dict(act)})
    out.append({"op": "flatten"})
    for i, (cin, cout) in enumerate(cfg["fcs"]):
        last = i == len(cfg["fcs"]) - 1
        out.append({"op": "fc", "cin": cin, "cout": cout,
                    "w_bits": cfg["weight_bits"],
                    "w_scale_log2": next(scales),
                    "act": None if last else dict(act)})
    return out
