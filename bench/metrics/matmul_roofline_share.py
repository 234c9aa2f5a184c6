"""matmul_roofline_share: the least time the logical work of every linear
layer call in the traced window could take on the chip (bench/work.py:
the larger of operations over the int8 peak and bytes over HBM
bandwidth), over the device time of those calls' matmul kernel events.

Only server calls with one matmul kernel event per linear layer count;
where none has (a kernel left the path, or the trace names it otherwise)
the metric is left out."""


def read(run):
    import work

    return work.roofline_share(run, "matmul")
