"""images_per_s: images resolved with logits per second of the window.

The window closes with the last flush that started before its end, so
the rate covers whole flushes: all the work and all the time between the
window's opening and that flush's resolution."""


def read(run):
    ends = [t1 for t0, t1, _ in run.calls if t0 < run.t_end]
    if not ends:
        return None
    t_close = max(ends)
    images = sum(r.images for r in run.requests
                 if r.ticket.ok and r.resolved <= t_close)
    return images / (t_close - run.t0)
