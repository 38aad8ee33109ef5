"""Share of the batches the window's later Newton steps walked that were
kept on the chip from the fit's first walk (``extra["newton"]``:
``batches_from_kept`` over it plus ``batches_put_again``), in percent: 100
where the labelled rows crossed once and every step after the first read
them from device memory. None where the program reports no such counters
or stepped over nothing kept or put again."""


def read(ctx):
    newton = ctx["load_module"]("work/newton.py").window_newton(ctx)
    if newton is None:
        return None
    kept = sum(fit["batches_from_kept"] for fit in newton)
    stepped = kept + sum(fit["batches_put_again"] for fit in newton)
    return 100.0 * kept / stepped if stepped else None
