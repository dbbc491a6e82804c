"""Operations the algorithm needs, from shapes (never from XLA's
cost analysis, which counts what the compiler chose to execute).

One multiply-add is two operations. Bias adds, ReLUs, pooling and the
small dense value head are left out: together they are under 0.1 % of
a forward pass of these nets.
"""

from __future__ import annotations


def conv_flops(points: int, width: int, c_in: int, c_out: int) -> int:
    """One SAME-padded ``width``×``width`` convolution over ``points``
    board points, counted dense (padding taps included, as the MXU
    computes them)."""
    return 2 * points * width * width * c_in * c_out


def forward_flops(net: dict, board: int) -> int:
    """Forward operations per position of one network group of a
    configuration file: a ``filter_width_1`` convolution from the
    input planes, ``layers - 2`` convolutions of ``filter_width_K``
    and the 1×1 head (to one channel for the policy, to
    ``head_filters`` for the FCN value head)."""
    points = board * board
    f = net["filters_per_layer"]
    total = conv_flops(points, net["filter_width_1"],
                       net["input_planes"], f)
    total += (net["layers"] - 2) * conv_flops(
        points, net["filter_width_K"], f, f)
    total += conv_flops(points, 1, f, net.get("head_filters", 1)
                        if net["class"] == "CNNValue" else 1)
    return total


def train_step_flops(net: dict, board: int, batch: int) -> int:
    """Forward + backward of one SGD step: 3 × forward (the backward
    pass is one product for the activations' gradient and one for the
    weights'). Recomputation does not count."""
    return 3 * forward_flops(net, board) * batch
