"""Shared test fixtures.

IMPORTANT: no XLA_FLAGS here — smoke tests and benchmarks must see the
real single CPU device (DESIGN.md §7).  Multi-device behaviour is tested in
subprocesses that set --xla_force_host_platform_device_count themselves
(see tests/util.py).
"""

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def tpu_interpret(monkeypatch):
    """Steer ``kernels.ops``' automatic dispatch onto the TPU kernels, run
    in Pallas's TPU interpret mode on the CPU: the served path's default
    choices (kernel per op, fallback counters) as they would be on a chip.
    Process-wide (not a thread-local context), so server and drain threads
    see it too."""
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels import ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    ops.reset_dispatch_stats()
    pltpu.set_tpu_interpret_mode()
    try:
        yield
    finally:
        pltpu.set_tpu_interpret_mode(None)  # back to compiled-or-error
        ops.reset_dispatch_stats()
