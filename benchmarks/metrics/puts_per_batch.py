"""Puts a host batch crossed the link in, over the window's fits: Σ
``extra["ingest"]["batches"]`` (the puts; since the program cuts a host
batch over ``PUT_BYTES_MAX`` into equal row slices, each put is a slice,
``spark_rapids_ml_tpu/ops/streaming.py`` ``put_slices``) ÷ Σ
``host_batches`` (the batches the source yielded in the fits' walks). 2.0
where each host batch crosses in two slices, 1.0 where batches cross whole.
None where the program has no door for its reports or a fit has no
``host_batches`` (a program that puts host batches whole, without the
counter)."""


def read(ctx):
    crossing = ctx["load_module"]("work/crossing.py")
    ingest = crossing.window_ingest(ctx)
    if ingest is None:
        return None
    puts = crossing.counter_sum(ingest, "batches")
    host = crossing.counter_sum(ingest, "host_batches")
    return None if puts is None or not host else puts / host
