"""Share of the window's fits whose shifted Gram was accepted
(``extra["ingest"]["gram_shift"]["accepted"]``: a two-pass fit sums the
centred Gram about its first batch's mean while the rows cross, re-centres
it once the mean of all rows is known, and runs its second pass only where
the rows say the shift cost digits), in percent: 100 in a cell whose Gram
steps run under the crossing. None where the program has no door for its
reports or a fit has no such counter (a program without the shift, a
one-pass fit)."""


def read(ctx):
    crossing = ctx["load_module"]("work/crossing.py")
    ingest = crossing.window_ingest(ctx)
    if ingest is None:
        return None
    verdicts = [(fit.get("gram_shift") or {}).get("accepted")
                for fit in ingest]
    if any(v is None for v in verdicts):
        return None
    return 100.0 * sum(map(bool, verdicts)) / len(verdicts)
