"""Share of the batches the window's Lloyd walks stepped that were kept on
the chip from the fit's first walk (``extra["lloyd"]``:
``batches_from_kept`` over it plus ``batches_put_again``), in percent: 100
where the rows crossed once and every step and the cost pass read them
from device memory. None where the program reports no such counters or
stepped nothing."""


def read(ctx):
    lloyd = ctx["load_module"]("work/lloyd.py").window_lloyd(ctx)
    if lloyd is None:
        return None
    kept = sum(fit["batches_from_kept"] for fit in lloyd)
    stepped = kept + sum(fit["batches_put_again"] for fit in lloyd)
    return 100.0 * kept / stepped if stepped else None
