"""The driver entrypoints, suite-guarded.

``__graft_entry__`` is what the round driver actually runs (single-chip
compile check + the multi-chip dry run that produces MULTICHIP_r0N);
a wiring regression there would silently cost the round its
driver-captured artifact, so the suite executes both entrypoints —
``entry()`` jitted end-to-end and the FULL dryrun at 4 devices (every
SPMD path plus the 2-process multihost job, ~100s on the virtual CPU
mesh; the driver runs the same code at 8).
"""

import numpy as np


def test_entry_compiles_and_runs():
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    components, evr, mean = jax.jit(fn)(*args)
    assert components.shape == (128, 16)
    assert np.isfinite(np.asarray(components)).all()
    assert np.isfinite(np.asarray(evr)).all()
    assert mean.shape == (128,)


def test_dryrun_multichip_executes_every_path():
    import __graft_entry__ as g

    # 4 devices: even count (the dp×tp grid needs one), half the
    # driver's 8 for suite wall-clock; asserts live inside the dryrun
    g.dryrun_multichip(4)
