"""Multi-host bootstrap around ``jax.distributed`` (the fleet tier).

The paper's deployment is a *fleet* of hosts each sketching its local
traffic, merged into one answer (full mergeability, Algorithm 4).  On the
device tier that fleet is a ``keys`` mesh spanning every process's devices
(``launch.mesh.make_keys_mesh``): each host ingests only the rows it owns
and the only cross-host traffic is the rollup psum — the Cafaro-style
hierarchical DDSketch fusion as one collective.

This module owns process bootstrap:

* ``initialize()`` wraps ``jax.distributed.initialize`` with coordinator /
  process-count / process-id resolution from arguments or the
  ``REPRO_COORDINATOR`` / ``REPRO_NUM_PROCESSES`` / ``REPRO_PROCESS_ID``
  environment (one env per launcher line: ``REPRO_COORDINATOR=host0:1234
  REPRO_NUM_PROCESSES=8 REPRO_PROCESS_ID=3 python -m ...``), and is a
  **single-process no-op** when neither names more than one process — the
  same entry points serve a laptop smoke run and an 8-host fleet.
* CPU fleets (the CI simulation tier and host-side aggregators) get the
  gloo collectives backend selected automatically — XLA's CPU client needs
  it for cross-process psum/all_gather.
* ``barrier()`` / ``process_index()`` / ``process_count()`` are the tiny
  process-topology helpers the checkpoint tier and benches share; all of
  them degrade to single-process answers when distributed never started.

Call ``initialize()`` before any other jax API touches the backend:
device counts and collectives are fixed at first backend use.
"""

from __future__ import annotations

import os
import socket
import time

import jax

__all__ = [
    "initialize",
    "shutdown",
    "is_distributed",
    "process_index",
    "process_count",
    "is_coordinator",
    "barrier",
]

_ENV_COORDINATOR = "REPRO_COORDINATOR"
_ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
_ENV_PROCESS_ID = "REPRO_PROCESS_ID"
_ENV_LOCAL_DEVICES = "REPRO_LOCAL_DEVICES"
_ENV_PREFLIGHT_TIMEOUT = "REPRO_PREFLIGHT_TIMEOUT"
_ENV_PREFLIGHT_RETRIES = "REPRO_PREFLIGHT_RETRIES"

_initialized = False


def _env_int(name: str) -> int | None:
    raw = os.environ.get(name)
    return None if raw in (None, "") else int(raw)


def _env_float(name: str) -> float | None:
    raw = os.environ.get(name)
    return None if raw in (None, "") else float(raw)


def _tcp_preflight(
    coordinator: str,
    deadline_s: float,
    *,
    retries: int | None = None,
    attempt_timeout_s: float = 1.0,
    backoff_s: float = 0.25,
) -> None:
    """Wait (bounded) for the coordinator's TCP port to accept connections.

    ``jax.distributed``'s own client turns an unreachable coordinator into
    a *fatal process abort* (C++ ``LOG(FATAL)`` on RegisterTask deadline) —
    uncatchable from Python.  Probing the socket first converts "nothing is
    listening" into an ordinary ``ConnectionError`` callers can handle (the
    CI harness maps it to a clean skip).

    ``deadline_s`` bounds total wall time; ``retries`` additionally caps
    connect attempts (None = attempts until the deadline) — slow-booting
    coordinators get the full budget, a truly dead one gives up after a
    known attempt count.  Attempts back off with a short growing sleep
    (``backoff_s`` doubling, capped at 2s) so the probe doesn't hammer a
    booting host.
    """
    host, _, port = coordinator.rpartition(":")
    deadline = time.monotonic() + deadline_s
    attempt = 0
    while True:
        try:
            with socket.create_connection(
                (host or "localhost", int(port)), attempt_timeout_s
            ):
                return
        except OSError as e:
            attempt += 1
            out_of_budget = time.monotonic() >= deadline or (
                retries is not None and attempt > retries
            )
            if out_of_budget:
                raise ConnectionError(
                    f"coordinator {coordinator} unreachable after "
                    f"{attempt} attempt(s) / {deadline_s:.0f}s budget"
                ) from e
            time.sleep(min(backoff_s * (2 ** (attempt - 1)), 2.0))


def initialize(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
    *,
    local_device_count: int | None = None,
    timeout_s: int | None = None,
    preflight_timeout_s: float | None = None,
    preflight_retries: int | None = None,
) -> bool:
    """Join (or skip joining) the fleet; returns True iff distributed.

    Arguments fall back to the ``REPRO_*`` environment, so launchers can
    configure the fleet without touching call sites.  With fewer than two
    processes resolved this is a **no-op returning False** — every caller
    (serve, benches, tests) can call it unconditionally.

    ``local_device_count`` forces the per-process CPU device count (the
    simulation knob: N fake devices per process via XLA_FLAGS); it must be
    applied before jax initializes its backend, so pass it only from true
    entry points.  ``timeout_s`` bounds the coordinator handshake — the CI
    harness uses a short timeout so an unreachable coordinator surfaces as
    a clean skip rather than a hung job.

    ``preflight_timeout_s`` / ``preflight_retries`` (env
    ``REPRO_PREFLIGHT_TIMEOUT`` / ``REPRO_PREFLIGHT_RETRIES``) tune the
    non-coordinator TCP probe: the timeout is the total wall budget to
    wait for the coordinator port (default: ``timeout_s``), the retry
    count caps connect attempts — raise the budget for slow-booting
    coordinators so they aren't misreported as unreachable, lower the
    retries for fail-fast chaos/CI lanes.
    """
    global _initialized
    coordinator = coordinator or os.environ.get(_ENV_COORDINATOR) or None
    num_processes = (
        num_processes if num_processes is not None else _env_int(_ENV_NUM_PROCESSES)
    )
    process_id = process_id if process_id is not None else _env_int(_ENV_PROCESS_ID)
    if local_device_count is None:
        local_device_count = _env_int(_ENV_LOCAL_DEVICES)

    if local_device_count is not None:
        flag = f"--xla_force_host_platform_device_count={int(local_device_count)}"
        prev = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in prev:
            os.environ["XLA_FLAGS"] = f"{prev} {flag}".strip()

    if _initialized:
        return True
    if coordinator is None or num_processes is None or int(num_processes) <= 1:
        return False  # single process: plain local jax, nothing to join

    # XLA's CPU client only speaks cross-process collectives through gloo;
    # select it before the backend exists (ignored by the TPU client)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    if preflight_timeout_s is None:
        preflight_timeout_s = _env_float(_ENV_PREFLIGHT_TIMEOUT)
    if preflight_retries is None:
        preflight_retries = _env_int(_ENV_PREFLIGHT_RETRIES)

    kwargs = {}
    if timeout_s is not None:
        kwargs["initialization_timeout"] = int(timeout_s)
    budget = (
        preflight_timeout_s
        if preflight_timeout_s is not None
        else (None if timeout_s is None else float(timeout_s))
    )
    if budget is not None and process_id is not None and int(process_id) != 0:
        # process 0 *is* the coordinator (it binds the port); everyone
        # else probes reachability first so a dead coordinator raises
        # instead of fatally aborting the process
        _tcp_preflight(coordinator, budget, retries=preflight_retries)
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=int(num_processes),
        process_id=None if process_id is None else int(process_id),
        **kwargs,
    )
    _initialized = True
    return True


def shutdown() -> None:
    """Leave the fleet (idempotent); test harnesses call this on teardown."""
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def is_distributed() -> bool:
    """True iff this process joined a multi-process fleet."""
    return _initialized or jax.process_count() > 1


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_coordinator() -> bool:
    """True on the process that owns coordinator duties (writes, logs)."""
    return jax.process_index() == 0


def barrier(tag: str = "repro") -> None:
    """Block until every process reaches this point (single-process no-op).

    The checkpoint tier uses it to order process-0 writes before anyone
    restores; benches use it to fence timed regions across the fleet.
    """
    if not is_distributed():
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(tag)
