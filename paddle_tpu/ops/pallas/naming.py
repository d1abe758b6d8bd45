"""How every Pallas kernel of this package is called, so that a trace
can find it after a refactor.

``named_pallas_call(name, kernel, ...)`` is ``pl.pallas_call(kernel,
name=name, ...)`` traced under ``jax.named_scope("pt.kernel.<name>")``.
The ``name`` is the kernel's name in the lowered program (``kernel_name``
of the Mosaic custom call: what ``program_costs`` of the serving engine
and ``chip_smoke.py`` read) and, on this runtime, the name of the
compiled custom call's HLO instruction (``%flash_fwd.1``), which is
what the device trace names an op by. The scope puts every kernel under
one prefix in the op's ``op_name``
(``.../pt.kernel.flash_fwd/flash_fwd/pallas_call``). Names are stable,
with no space and no ``=``.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl

KERNEL_SCOPE = "pt.kernel."


def named_pallas_call(name: str, kernel, **kwargs):
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def run(*args):
        with jax.named_scope(KERNEL_SCOPE + name):
            return call(*args)

    return run
