"""The held experts' gate and up matrices between the two trees the tests
of `deepseek_v3`, `ling3` and `dots3` compare: the benchmark's references
draw `wg` and `wu` [held, D, F] apart, the programs' `init` lays them side
by side in one leaf `wgu` [held, D, 2F] (`ops.moe.held_experts_leaf`).
A layer without routed experts comes back as it is."""
from ray_tpu.ops.moe import held_experts_leaf


def laid(layer):
    """A layer of a reference's draw as the programs' tree lays it."""
    if "wg" not in layer:
        return layer
    rest = {k: v for k, v in layer.items() if k not in ("wg", "wu")}
    return dict(rest, wgu=held_experts_leaf(layer["wg"], layer["wu"]))


def apart(layer):
    """A layer of the programs' tree as a reference reads it."""
    if "wgu" not in layer:
        return layer
    F = layer["wd"].shape[1]
    rest = {k: v for k, v in layer.items() if k != "wgu"}
    return dict(rest, wg=layer["wgu"][..., :F], wu=layer["wgu"][..., F:])
