"""conv_res_roofline_share: the least time the logical work of every
residual join conv (a block's conv_b with its skip operand read in the
epilogue) in the traced window could take on the chip (bench/work.py:
the larger of operations over the int8 peak and bytes over HBM
bandwidth), over the device time of those calls' ``conv_res`` kernel
events (``radix_conv2d_res_pallas``).

Only server calls with one such event per join count; where none has,
or the family has no ``conv_res`` kernel, the metric is left out."""


def read(run):
    import work

    return work.roofline_share(run, "conv_res")
