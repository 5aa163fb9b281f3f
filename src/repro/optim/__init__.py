"""Unified staleness-aware optimizer subsystem (DESIGN.md §3).

The single source of truth for the paper's applyUpdate hot-spot: every
protocol's weight update — hardsync Eq. 3, n-softsync Eq. 5, async Eq. 4,
with the staleness LR modulation of Eq. 6 / footnote 3 — is expressed once
(``spec.update_event``) and executed by three interchangeable backends
(``reference`` / ``jit`` / ``pallas``).  ``core/protocols.py``,
``core/distributed.py``, ``core/simulator.py`` and ``train/loop.py`` all
route through this module; the fused Pallas ``ps_update`` kernel shares the
same event body, making the optimized path the measured path.
"""

from repro.optim.spec import (KERNEL_OPTIMIZERS, OPTIMIZERS, RoundFold,
                              UpdateSpec, init_state, sequential_fold,
                              spec_from_run, update_event)
from repro.optim.backends import (BACKENDS, apply_event_flat,
                                  apply_event_ring, apply_event_ring_whatif,
                                  apply_round_folded, apply_single,
                                  apply_update, apply_update_tree,
                                  apply_update_flat, combine_spmd, sgd_step)
from repro.optim import flatten  # noqa: F401

__all__ = [
    "OPTIMIZERS", "KERNEL_OPTIMIZERS", "BACKENDS",
    "UpdateSpec", "RoundFold", "init_state", "spec_from_run",
    "update_event", "sequential_fold",
    "apply_update", "apply_update_tree", "apply_update_flat",
    "apply_event_flat", "apply_event_ring", "apply_event_ring_whatif",
    "apply_single", "apply_round_folded", "sgd_step", "combine_spmd",
]
