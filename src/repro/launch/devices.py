"""Forced-host-device and multi-process environment plumbing, shared by the
launchers.

XLA fixes its device count when the backend initializes — which importing
``repro.core`` already did by the time a driver parses its arguments — so a
driver that discovers it needs a wider host platform must re-exec itself
once with ``--xla_force_host_platform_device_count`` in ``XLA_FLAGS``.
Forced devices exist only on the CPU backend: on an accelerator that has
too few devices the launcher stops instead of falling back to the CPU.
That logic used to be grown ad hoc per flag (``--engine ring``,
``--data-shards``, ``--family-cache``) inside ``cges_run``; it lives here
now, and the same helper carries the ``jax.distributed`` coordinator
environment for the multi-process async-ring launch path
(``launch/ring_async_run.py``).
"""
from __future__ import annotations

import os
import sys
from typing import Dict, List, Optional


def force_host_devices_or_reexec(
    need: int,
    module: str,
    argv: Optional[List[str]] = None,
    extra_env: Optional[Dict[str, str]] = None,
) -> None:
    """Ensure at least ``need`` jax devices exist, re-exec'ing
    ``python -m <module> <argv>`` once with forced host devices if the
    already-initialized platform is too small.

    ``extra_env`` entries are exported before the re-exec (e.g. the
    ``jax.distributed`` coordinator triplet for a multi-process launch).
    Raises ``SystemExit`` if the device count was already forced and is
    still too small — re-exec'ing again would loop forever — and
    ``RuntimeError`` on any backend but the CPU, whose devices are real.
    """
    if need <= 1:
        return
    import jax

    if len(jax.devices()) >= need:
        return
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{module} needs >= {need} devices but this {backend} host has "
            f"{len(jax.devices())}; forced host devices exist only on the "
            f"CPU backend")
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" in flags:
        raise SystemExit(
            f"{module} needs >= {need} devices, found {len(jax.devices())} "
            f"(host platform device count already forced: {flags!r})")
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={need}").strip()
    for key, val in (extra_env or {}).items():
        os.environ[key] = val
    os.execv(sys.executable,
             [sys.executable, "-m", module]
             + (sys.argv[1:] if argv is None else argv))


def jax_distributed_env(coordinator: str, num_processes: int,
                        process_id: int) -> Dict[str, str]:
    """The env triplet a ring-async worker consumes to join the optional
    ``jax.distributed`` cluster (cluster formation only on the CPU backend —
    cross-process collectives aren't implemented there, and the coordination
    service hard-terminates survivors when a peer dies, so the data plane
    stays on our own sockets; see core/ring_async.py)."""
    return {
        "REPRO_JAX_COORDINATOR": coordinator,
        "REPRO_JAX_NUM_PROCS": str(int(num_processes)),
        "REPRO_JAX_PROC_ID": str(int(process_id)),
    }
