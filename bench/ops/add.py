"""``add``: the elementwise sum of two inputs of one shape (a residual
join).

    {"op": "add", "in": [i, j], "act"}

In the graph, ``Add [-> Relu] [-> Quant]``.
"""


def shape(layer: dict, in_shapes: list) -> tuple:
    if len(in_shapes) != 2 or tuple(in_shapes[0]) != tuple(in_shapes[1]):
        raise ValueError(f"add needs two inputs of one shape; got "
                         f"{in_shapes}")
    return tuple(in_shapes[0])


def macs(layer: dict, in_shapes: list) -> int:
    return 0


def graph(b, layer: dict, inputs: list, qweight) -> str:
    (h,) = b.add_node("Add", inputs, 1)
    return h


def reference(layer: dict, xs: list, w, control):
    return xs[0] + xs[1]
