"""The stencil kernels' share of the HBM roofline: the operand and result
bytes of every Mosaic call, over the calls' device time, over the chip's
peak bandwidth (benchmark/peaks.json).  Memory bound: the kernels do a
few flops per byte, far under the chip's flops per byte."""


def read(outcome, reduced, ctx):
    return reduced.kernel_roofline_share()
