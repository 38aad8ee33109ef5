"""Bytes handed to ``device_put`` over the seconds of the estimator's
``covariance`` phase (``fit_timings_["covariance"]``), in GB/s. Transfer
and accumulate kernels both sit inside that phase and cannot be told
apart from outside the program, so this is the phase's rate, not the
link's."""


def read(ctx):
    seconds = [f["timings"].get("covariance") for f in ctx["fits"]]
    if not seconds or any(s is None for s in seconds) or not sum(seconds):
        return None
    return ctx["bytes_put_per_fit"] * len(seconds) / sum(seconds) / 1e9
