"""mfu_int8: the whole step's share of the chip's int8 peak: images per
second times logical operations per image (bench/work.py) over chips
times the peak.

A per-layer metric, so it is read in the traced run: the images per
second are those of that run's window (the cell's ``trace_seconds``,
under the profiler, with a host annotation on every submit and server
call), not of the untraced run's window that ``images_per_s`` reports."""


def read(run):
    import readers
    import work

    rate = readers.load("images_per_s")(run)
    if not rate:
        return None
    ops = work.ops_per_image(run.cfg)
    return 100.0 * rate * ops / (run.chips * run.peak["int8_ops_per_s"])
