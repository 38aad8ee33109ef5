"""Share of the chips' idle seconds in the traced window during which no
put of that chip was outstanding (outside every ``stream:landing/<id>``
span of the chip's own watcher), over all traced chips, in percent. Idle
seconds under a landing can only go with fewer bytes or work moved under
the crossing; idle seconds outside one are the host's to give back. None
without a device trace or where the trace holds no landing span."""

import sys


def read(ctx):
    trace = ctx["trace"]
    if not trace or trace["busy_s"] is None:
        return None
    crossing = ctx["load_module"]("work/crossing.py")
    chips = crossing.idle_outside_crossing(trace,
                                           ctx["load_module"]("xplane.py"))
    if not chips:
        return None
    idle = sum(crossing.seconds(i) for i, _ in chips.values())
    outside = sum(crossing.seconds(o) for _, o in chips.values())
    print("chip-idle seconds outside a landing: " + ", ".join(
        f"chip {chip} {crossing.seconds(o):.3f} of {crossing.seconds(i):.3f}"
        for chip, (i, o) in sorted(chips.items())),
        file=sys.stderr, flush=True)
    return 100.0 * outside / idle if idle else None
